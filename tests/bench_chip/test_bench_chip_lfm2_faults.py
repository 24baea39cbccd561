"""The check catches a broken timed path in the LFM2 decode cell, as
`test_bench_chip_faults` shows for the other cells: conv state and KV
beside each other, the step donating its state, the experts hit copied
after the tokens. Each fault comes out not `correct`; the sound run
comes out `correct`."""
import jax
import jax.numpy as jnp
import pytest

from lfm2_smoke import lfm2_cell
from test_bench_chip_faults import (AnswerAltered, HalfBatch,
                                    StateUnchanged, _measure, _setup)


class DonatedStateUnchanged(StateUnchanged):
    """The step computes its outputs but hands back a copy of the state
    it got, made before the step: a step that donates its state consumes
    what it got."""

    def step(self, state, feed, positions):
        kept = jax.tree.map(jnp.copy, state)
        rows, _ = self.inner.step(state, feed, positions)
        return rows, kept


@pytest.fixture(scope="module")
def lfm2_setup():
    mp = pytest.MonkeyPatch()
    st = _setup(lfm2_cell(), mp)
    yield st
    mp.undo()


@pytest.mark.parametrize("fault", [None, DonatedStateUnchanged, HalfBatch,
                                   AnswerAltered],
                         ids=["sound", "state_unchanged", "half_batch",
                              "token_altered"])
def test_lfm2_fault_fails_the_check(lfm2_setup, fault):
    out = _measure(lfm2_setup, fault)
    assert out["correct"] is (fault is None), out["check"]
