"""The LM jobs' guard on ``sliding_window``: a window shorter than the
traffic's ``seq_len`` is refused unless the configuration's
``program_argument_of`` hands it to a program argument, and then the
program's ``TransformerConfig`` must have that argument at the published
value. No program is built."""

import dataclasses

import pytest

from benchmark import harness

ROOT = harness.ROOT
WINDOW = 1024


def _job(cell):
    return harness.load_module(ROOT, "jobs", cell.job).Job(
        cell, seed=0, tracked=False, work_dir="", log=lambda s: None)


def _windowed(cell):
    """The cell's file with a 1,024-token window handed to the program, at
    the traffic's 8,192 tokens a sequence."""
    config = cell.config
    return dataclasses.replace(cell, config=dict(
        config, sliding_window=WINDOW,
        program_argument_of=dict(config["program_argument_of"],
                                 sliding_window="sliding_window"),
        program=dict(config["program"], sliding_window=WINDOW)))


def test_a_window_the_file_keeps_from_the_program_is_refused():
    """Mistral's files map nothing: past their 4,096-token window the job
    refuses, naming the window."""
    cell = harness.load_cell("pretrain_mistral7b_1chip")
    assert "program_argument_of" not in cell.config
    assert _job(cell).seq_len == 4096
    longer = dataclasses.replace(cell, traffic=dict(cell.traffic, seq_len=8192))
    with pytest.raises(ValueError, match="sliding_window 4096, and its "
                       "program_argument_of hands sliding_window to no"):
        _job(longer)


def test_a_window_handed_to_an_argument_the_program_lacks_is_refused():
    """Today's ``TransformerConfig`` has no window: the program would ignore
    the argument, so the job says it cannot build the file."""
    cell = harness.load_cell("pretrain_ling3_ep64_1chip")
    assert int(cell.traffic["seq_len"]) > WINDOW
    with pytest.raises(RuntimeError, match=r"cannot build ling3\.0_flash_ep64_l7:"
                       r" its TransformerConfig has no \['sliding_window'\]"):
        _job(_windowed(cell))


def test_a_window_the_program_takes_builds_the_same_job(monkeypatch):
    """With the field present (a stand-in for a program that has it), the job
    is the one it was, its program carrying the window; a program argument
    that differs from the published window is still refused."""
    from fedml_tpu.parallel import transformer

    plain = _job(harness.load_cell("pretrain_ling3_ep64_1chip"))

    @dataclasses.dataclass(frozen=True)
    class WithWindow(transformer.TransformerConfig):
        sliding_window: int = 0

    monkeypatch.setattr(transformer, "TransformerConfig", WithWindow)
    cell = _windowed(harness.load_cell("pretrain_ling3_ep64_1chip"))
    job = _job(cell)
    assert (job.seq_len, job.batch) == (plain.seq_len, plain.batch) == (8192, 1)
    assert job.program == dict(plain.program, sliding_window=WINDOW)
    wrong = dataclasses.replace(cell, config=dict(
        cell.config, program=dict(cell.config["program"], sliding_window=2048)))
    with pytest.raises(ValueError, match="sliding_window is 1024 but the "
                       "program's argument sliding_window is 2048"):
        _job(wrong)
