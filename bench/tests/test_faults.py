"""A run with its timed path broken underneath comes out not correct.

Each test skips the look for a chip and drives the rest of a run at a
size the CPU holds, with one fault planted in the program: a train step
that returns its state unchanged, a train step that sees half of its
batch (the sum taken over the rest, doubled), and a scorer that alters
an answer where it is produced.  A run without a fault is correct."""
import time

import pytest

import tiny
from harness import runner


def _run(cell, seed=2**33 + 5):
    return runner.run_cell(cell, seed, 0.5, False, tiny.devices(),
                           tiny.PEAKS, time.perf_counter())


@pytest.mark.parametrize("traffic", [("train", {}),
                                     ("score_poisson", {"rate_per_s": 100})],
                         ids=["train", "score"])
def test_sound_run_is_correct(traffic):
    run = _run(tiny.cell("speech-100k", traffic[0], **traffic[1]))
    assert run.correct, run.checks


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.core import TraTrainer
    monkeypatch.setattr(TraTrainer, "step", tiny.frozen(TraTrainer.step))
    run = _run(tiny.cell("speech-100k", "train"))
    assert not run.correct
    assert run.checks["change_gap"][0] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    from repro.core import TraTrainer
    monkeypatch.setattr(TraTrainer, "step", tiny.half_batch(TraTrainer.step))
    run = _run(tiny.cell("speech-100k", "train"))
    assert not run.correct


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.serve import FFNNScorer
    unpack = FFNNScorer.unpack

    def altered(self, outs, n):
        answers = unpack(self, outs, n)
        answers[0] = answers[0].copy()
        answers[0][0] += 0.05
        return answers

    monkeypatch.setattr(FFNNScorer, "unpack", altered)
    run = _run(tiny.cell("speech-100k", "score_poisson", rate_per_s=100))
    assert not run.correct
