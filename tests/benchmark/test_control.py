"""The comparison that decides `correct` can fail: the reference computed on
bfloat16 prices and put in the program's place comes out not correct, the
same at full precision comes out correct; and a run whose timed path is
broken underneath (an answer altered, or a batch of answers dropped, where
the engine hands them over) reports `correct` false."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import numpy as np
import pytest

from benchmark import compare, control, harness, manifest


def _cell(name):
    cell = manifest.Manifest().cell(name)
    cell["config"] = manifest.rehearsed(cell["config"])
    cell["traffic"] = manifest.rehearsed(cell["traffic"])
    return cell


@pytest.mark.parametrize("name,batches", [("pattern1k.sat", 6),
                                          ("filter1q.sat", 260)])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_lower_precision_in_the_programs_place_fails(name, batches, seed):
    cell = _cell(name)
    sound = control.stand_in(cell, seed, batches, lower=False)
    assert compare.verdict(sound), sound
    lowered = control.stand_in(cell, seed, batches, lower=True)
    assert not compare.verdict(lowered)
    assert sum(c["value"] > c["limit"] for c in lowered) >= 1


def _drive(name, monkeypatch, corrupt):
    """The in-process driver, the harness's look for a chip skipped, with
    `corrupt` between the engine and every batch callback."""
    import jax
    from siddhi_tpu.core.runtime import SiddhiAppRuntime
    real = SiddhiAppRuntime.add_batch_callback
    seen = [0]

    def add(self, stream_id, fn):
        def broken(b):
            seen[0] += 1
            out = corrupt(b, seen[0])
            if out is not None:
                fn(out)
        real(self, stream_id, broken)
    monkeypatch.setattr(SiddhiAppRuntime, "add_batch_callback", add)
    run = harness.Run(cell=_cell(name), seed=11, seconds=0.6, trace_on=False,
                      devices=jax.devices()[:1])
    driver = manifest.module("drivers", run.cell["traffic"]["driver"])
    return driver.run(run)


def test_sound_run_is_correct(monkeypatch):
    out = _drive("pattern1k.sat", monkeypatch, lambda b, i: b)
    assert out["correct"] is True and out["counts"]["rows_delivered"] > 0


def test_an_altered_answer_is_seen(monkeypatch):
    def alter(b, i):
        if i == 3 and b.n:
            b.columns["p2"] = np.array(b.columns["p2"])
            b.columns["p2"][0] += 0.25
        return b
    out = _drive("pattern1k.sat", monkeypatch, alter)
    assert out["correct"] is False


@pytest.mark.parametrize("name", ["pattern1k.sat", "filter1q.sat"])
def test_a_dropped_batch_of_answers_is_seen(monkeypatch, name):
    out = _drive(name, monkeypatch, lambda b, i: None if i == 3 else b)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"])
