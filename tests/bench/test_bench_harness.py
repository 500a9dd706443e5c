"""BENCHMARK.json's schema, the harness finding configs, traffic, graphs
and metric readers by name, and bench/run.py refusing without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench
from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(bench.__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_schema():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]]
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert c["file"].startswith("bench/") and 1 <= len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in b["workloads"])
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(b["workloads"])
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in b["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        # every cell a metric lists reports the metric it moves
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads",
                                                               cells))
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_harness_finds_each_cell_by_name(cell):
    b, c, cfg, traffic = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                                       cell)
    assert c["name"] == cell and cfg["name"] == c["config"]
    assert os.path.exists(os.path.join(ROOT, "bench", "graphs",
                                       cfg["family"] + ".py"))
    assert os.path.exists(os.path.join(ROOT, "bench",
                                       traffic["kind"] + ".py"))
    assert set(cfg["limits"]) >= ({"served_rel_err"}
                                  if traffic["kind"] == "serve"
                                  else {"out_rel_err", "layer_rel_err"})


@pytest.mark.parametrize("metric", [m["name"] for m in _bench()["per_layer"]])
def test_each_per_layer_metric_has_a_reader_that_reads_nothing_elsewhere(
        metric):
    # a reader given a record of another kind of cell returns None, so
    # the harness leaves the metric out rather than report 0
    assert run.read_metric(metric, {"kind": "other"}) is None


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "vgg16.offline-b32", "--seed", "3000000001",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_fails_in_a_checkout_of_only_the_benchmark(tmp_path):
    b = _bench()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in b["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "resnet18.offline-b1", "--seed", "5", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
