"""A whole offline run at the tiny ResNet test configuration (a lowered
stem and transition, a direct projection): sound, under the int4
control, and with half of each batch left out."""
from runs import plant, run_cell


def test_sound_run_is_correct(capsys, no_compile_cache):
    rc, line, _ = run_cell(capsys, "tiny_resnet.offline")
    assert rc == 0 and line["correct"] is True and line["failed"] == 0


def test_int4_control_is_not_correct(capsys, no_compile_cache):
    rc, line, _ = run_cell(capsys, "tiny_resnet.offline", "--control",
                           "int4")
    assert rc == 0 and line["correct"] is False


def test_half_batch_left_out_is_not_correct(capsys, monkeypatch,
                                            no_compile_cache):
    plant(monkeypatch, "half_batch")
    rc, line, _ = run_cell(capsys, "tiny_resnet.offline")
    assert rc == 0 and line["correct"] is False
