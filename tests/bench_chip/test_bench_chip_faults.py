"""The check catches a broken timed path: each fault a serving cell can
have is planted under the program's adapter, the rest of the run goes on
as on the chip (the look for a chip skipped), and `correct` comes out
false. The same runs unbroken come out true."""
import time

import numpy as np
import pytest

from benchmarks.chip import run
from bench_chip_smoke import cnn_cell, lm_cell, no_persistent_cache

SEED = 2**32 + 4242


class StateUnchanged:
    """The step computes its outputs but hands back the state it got."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def step(self, state, feed, positions):
        rows, _ = self.inner.step(state, feed, positions)
        return rows, state


class HalfBatch(StateUnchanged):
    """Only the first half of the slots is computed; the second half
    gets the first half's rows."""

    def step(self, state, feed, positions):
        rows, state = self.inner.step(state, feed, positions)
        rows = np.array(rows)
        h = rows.shape[0] // 2
        rows[h:2 * h] = rows[:h]
        return rows, state


class AnswerAltered(StateUnchanged):
    """Every output row is changed where it is produced."""

    def step(self, state, feed, positions):
        rows, state = self.inner.step(state, feed, positions)
        rows = np.array(rows)
        if np.issubdtype(rows.dtype, np.integer):
            rows[:, 0] += 1                   # an integer logit off by one
        else:
            rows[:, 0] = rows.max(axis=-1) + 1.0   # token 0 always wins
        return rows, state


def _setup(cell, monkeypatch):
    """Offered far above what the CPU serves, so every slot is busy and
    a fault in any slot reaches the sample."""
    no_persistent_cache(monkeypatch)
    cell.traffic["rate_per_s"] = 400.0
    return run.setup(cell, seed=SEED, require_tpu=False,
                     t_start=time.perf_counter())


def _measure(st, fault):
    inner = st.adapter.inner
    if fault is not None:
        st.adapter.inner = fault(inner)
    try:
        return run.measure(st, seed=SEED, seconds=1.5, trace=False)
    finally:
        st.adapter.inner = inner


@pytest.fixture(scope="module")
def lm_setup():
    mp = pytest.MonkeyPatch()
    st = _setup(lm_cell(), mp)
    yield st
    mp.undo()


@pytest.fixture(scope="module")
def cnn_setup():
    mp = pytest.MonkeyPatch()
    st = _setup(cnn_cell(), mp)
    yield st
    mp.undo()


@pytest.mark.parametrize("fault", [None, StateUnchanged, HalfBatch,
                                   AnswerAltered],
                         ids=["sound", "state_unchanged", "half_batch",
                              "token_altered"])
def test_lm_fault_fails_the_check(lm_setup, fault):
    out = _measure(lm_setup, fault)
    assert out["correct"] is (fault is None), out["check"]


@pytest.mark.parametrize("fault", [None, HalfBatch, AnswerAltered],
                         ids=["sound", "half_batch", "answer_altered"])
def test_cnn_fault_fails_the_check(cnn_setup, fault):
    out = _measure(cnn_setup, fault)
    assert out["correct"] is (fault is None), out["check"]
