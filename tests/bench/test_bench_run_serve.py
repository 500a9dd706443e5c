"""A whole serving run at the tiny test configuration: sound, under the
int4 control, and with an answer altered where it is produced."""
from runs import plant, run_cell


def test_sound_run_is_correct_and_reports_latency(capsys, no_compile_cache):
    rc, line, err = run_cell(capsys, "tiny_vgg.serve")
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"p50_ms", "setup_s"}
    assert line["metrics"]["p50_ms"]["value"] > 0
    assert list(line)[-1] == "checks"
    assert err[-1].startswith("check served_rel_err ")


def test_int4_control_is_not_correct(capsys, no_compile_cache):
    rc, line, _ = run_cell(capsys, "tiny_vgg.serve", "--control", "int4")
    assert rc == 0 and line["correct"] is False


def test_altered_answer_is_not_correct(capsys, monkeypatch,
                                       no_compile_cache):
    plant(monkeypatch, "answer_altered")
    rc, line, _ = run_cell(capsys, "tiny_vgg.serve")
    assert rc == 0 and line["correct"] is False
