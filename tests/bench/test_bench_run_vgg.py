"""A whole offline run at the tiny VGG test configuration: sound, under
the int4 control, and with the timed path broken underneath."""
import pytest

from runs import FAULTS, plant, run_cell


def test_sound_run_is_correct_and_ends_with_the_result_line(
        capsys, no_compile_cache):
    rc, line, err = run_cell(capsys, "tiny_vgg.offline", seed=2 ** 31 + 11)
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 2 == 0
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert line["metrics"]["images_per_s"]["unit"] == "images/s"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    checks = line["checks"]
    assert set(checks) == {"out_rel_err", "layer_rel_err"}
    # the numbers compared are the last lines of standard error
    assert err[-2:] == [f"check {k} {v['value']!r} limit {v['limit']!r}"
                        for k, v in checks.items()]


def test_int4_control_is_not_correct(capsys, no_compile_cache):
    rc, line, _ = run_cell(capsys, "tiny_vgg.offline", "--control", "int4")
    assert rc == 0 and line["correct"] is False


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_timed_path_is_not_correct(capsys, monkeypatch,
                                          no_compile_cache, fault):
    plant(monkeypatch, fault)
    rc, line, _ = run_cell(capsys, "tiny_vgg.offline")
    assert rc == 0 and line["correct"] is False
