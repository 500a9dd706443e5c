"""A whole offline run at the tiny separable test configuration (a
depthwise 3x3/1 on the fused path, a direct 1x1, a direct depthwise
3x3/2 and a lowered ``groups=4`` 3x3/1): sound, under the int4 control,
and with the timed path broken underneath.  Its family module lives with
the tests and is entered as ``bench.graphs.tiny_separable`` here."""
import sys

import pytest

import tiny_separable
from runs import FAULTS, plant, run_cell


@pytest.fixture
def family(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.graphs.tiny_separable",
                        tiny_separable)


def test_sound_run_is_correct_and_plans_each_kind_of_layer(
        capsys, family, no_compile_cache):
    rc, line, err = run_cell(capsys, "tiny_separable.offline",
                             seed=2 ** 31 + 13)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    plans = next(e for e in err if e.startswith("bench: plans: "))
    assert plans == ("bench: plans: dw1 fast sfc4_4, pw1 direct direct, "
                     "dw2 direct direct, g lowered grouped[sfc4_4]")


def test_int4_control_is_not_correct(capsys, family, no_compile_cache):
    rc, line, _ = run_cell(capsys, "tiny_separable.offline", "--control",
                           "int4")
    assert rc == 0 and line["correct"] is False


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_timed_path_is_not_correct(capsys, monkeypatch, family,
                                          no_compile_cache, fault):
    plant(monkeypatch, fault)
    rc, line, _ = run_cell(capsys, "tiny_separable.offline")
    assert rc == 0 and line["correct"] is False
