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
    # every conv of a batch-32 forward: f32 in and out, int8 weights
    assert sum(work.least_bytes(l, 32) for l in convs) == 2_911_270_592


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
    assert sum(work.least_bytes(l, 1) for l in convs) == 29_832_384


@pytest.mark.parametrize("name,family", [("vgg16", vgg),
                                         ("resnet18", resnet)])
def test_a_layer_without_groups_counts_as_groups_one(name, family):
    cfg = _cfg(name)
    ca = cfg["counted_algo"]
    for l in L.conv_layers(family, cfg):
        assert "groups" not in l
        g1 = dict(l, groups=1)
        assert work.direct_macs(l) == work.direct_macs(g1)
        assert work.counted_ops(l, ca) == work.counted_ops(g1, ca)
        assert work.least_bytes(l, 32) == work.least_bytes(g1, 32)


def test_depthwise_counts_one_input_channel_per_output_channel():
    ca = _cfg("vgg16")["counted_algo"]
    dw = dict(kernel=3, stride=1, h=112, w=112, cin=96, cout=96, groups=96)
    assert work.direct_macs(dw) == 112 * 112 * 9 * 96
    assert work.counted_ops(dw, ca) == pytest.approx(
        2 * (100 / 36) * 112 * 112 * 96)
    assert work.least_bytes(dw, 1) == 112 * 112 * 96 * 4 * 2 + 9 * 96
    # stride 2 takes no SFC count: twice its direct MACs
    dw2 = dict(dw, stride=2)
    assert work.direct_macs(dw2) == 56 * 56 * 9 * 96
    assert work.counted_ops(dw2, ca) == 2 * 56 * 56 * 9 * 96


def test_grouped_counts_cin_over_groups_per_output_channel():
    g4 = dict(kernel=3, stride=1, h=28, w=28, cin=64, cout=64, groups=4)
    assert work.direct_macs(g4) == 28 * 28 * 9 * 16 * 64
    # the weights are (3, 3, 64 / 4, 64): 9 * 16 * 64 int8 bytes
    assert work.least_bytes(g4, 8) == 8 * 28 * 28 * 64 * 4 * 2 \
        + 9 * 16 * 64


def test_layers_whose_channels_do_not_divide_by_groups_are_refused():
    class Family:
        @staticmethod
        def layers(cfg):
            return [dict(name="g", kernel=3, stride=1, h=8, w=8, cin=12,
                         cout=16, groups=8)]
    with pytest.raises(ValueError, match="divide by groups 8"):
        L.conv_layers(Family, {})


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
