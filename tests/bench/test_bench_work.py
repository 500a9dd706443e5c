"""bench/work.py and bench/peaks.json against hand-worked counts."""
import json
import os

import pytest

import bench
from bench import work
from bench.graphs import layers as L
from bench.graphs import resnet, vgg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(bench.__file__)))


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_vgg16_direct_and_counted_gmac_per_image():
    cfg = _cfg("vgg16")
    convs = L.conv_layers(vgg, cfg)
    assert len(convs) == 13
    direct = sum(work.direct_macs(l) for l in convs)
    counted = sum(work.counted_ops(l, cfg["counted_algo"]) for l in convs)
    # 15.35 GMAC direct; SFC-6 counts t^2/M^2 = 100/36 per output and
    # channel pair instead of 9: 4.74 GMAC, 9.47 GOP
    assert direct == 15_346_630_656
    assert counted / 2 == pytest.approx(direct * (100 / 36) / 9)
    assert round(counted / 2 / 1e9, 2) == 4.74


def test_resnet18_direct_gmac_per_image():
    cfg = _cfg("resnet18")
    convs = L.conv_layers(resnet, cfg)
    assert len(convs) == 20       # stem + 16 block convs + 3 projections
    direct = sum(work.direct_macs(l) for l in convs)
    assert round(direct / 1e9, 2) == 1.81
    # stride-2 and 1x1 layers count 2 x direct MACs; only 3x3/1 use SFC
    stem = convs[0]
    assert work.counted_ops(stem, cfg["counted_algo"]) \
        == 2 * 112 * 112 * 49 * 3 * 64


def test_least_bytes_reads_input_and_weights_once_writes_output_once():
    l = dict(kernel=3, stride=1, h=56, w=56, cin=256, cout=256)
    per_image = 56 * 56 * 256 * 4 * 2
    assert work.least_bytes(l, 32) == 32 * per_image + 9 * 256 * 256


def test_least_time_names_its_bound():
    peaks = work.load_peaks("TPU v5 lite")
    ca = _cfg("vgg16")["counted_algo"]
    # with f32 activations in and out, SFC's 2 * 100/36 ops per channel
    # pair and pixel pass the v5e's 480 ops/byte ridge only past ~690
    # channels: every VGG-16 and ResNet-18 layer is bound by its bytes
    vgg_widest = dict(kernel=3, stride=1, h=14, w=14, cin=512, cout=512)
    assert work.least_time_s(vgg_widest, ca, 32, peaks)[1] == "bytes"
    wide = dict(kernel=3, stride=1, h=14, w=14, cin=1024, cout=1024)
    t, bound = work.least_time_s(wide, ca, 32, peaks)
    assert bound == "compute"
    assert t == pytest.approx(32 * work.counted_ops(wide, ca) / 393e12)
    thin = dict(kernel=1, stride=2, h=56, w=56, cin=64, cout=128)
    assert work.least_time_s(thin, ca, 32, peaks)[1] == "bytes"


def test_peak_table_has_v5e_and_refuses_unknown_devices():
    p = work.load_peaks("TPU v5 lite")
    assert (p["int8_ops_per_s"], p["bf16_flops_per_s"],
            p["hbm_bytes_per_s"]) == (393e12, 197e12, 819e9)
    with pytest.raises(KeyError):
        work.load_peaks("cpu")
