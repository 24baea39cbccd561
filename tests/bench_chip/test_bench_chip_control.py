"""The control of each cell's check comes out not correct: the plain
reference put in the program's place at the next lower precision (A4
activations where the configuration states A8) fails the limit that
sound runs of the program meet, and so does the program's own int8 KV
cache where the configuration states bf16, on three seeds, at a size a
test run can hold. The chip readings the full-size limits were set from
are in PERF.md."""
import time

import pytest

from benchmarks.chip import run
from bench_chip_smoke import (LM_SMOKE_LIMIT, cnn_cell, lm_cell,
                              no_persistent_cache)

SEEDS = [11, 12, 2**35 + 13]
A4 = {"a_bits": 4}


def _readings(cell, monkeypatch, serving=None):
    """(result, A4 control) per seed; with ``serving`` the program is
    built at those serving entries and checked against the stated ones,
    as `control.py --serving` does."""
    no_persistent_cache(monkeypatch)
    stated = dict(cell.config.get("serving", {}))
    cell.config.get("serving", {}).update(serving or {})
    st = run.setup(cell, seed=SEEDS[0], require_tpu=False,
                   t_start=time.perf_counter())
    cell.config.get("serving", {}).update(stated)
    out = []
    for i, seed in enumerate(SEEDS):
        if i:
            st.served.load(seed)
            st.adapter.inner = st.served.rebind(st.adapter.inner)
            run.warm_up(st.adapter, st.served.warm_payloads(),
                        cell.traffic["slots"], st.mesh)
        res = run.measure(st, seed=seed, seconds=1.5, trace=False)
        out.append((res, st.served.control(st.last_window, seed, A4)))
    return out


def test_lm_control_fails_the_limit(monkeypatch):
    for res, control in _readings(lm_cell(), monkeypatch):
        assert res["correct"] is True
        assert res["check"]["kv_bits_short"]["value"] == 0
        assert control["max_logit_gap"] > LM_SMOKE_LIMIT


def test_lm_int8_kv_cache_fails_the_check(monkeypatch):
    """The program's int8 KV path where the configuration states bf16:
    the cache the steps carry is 8 bits short, whatever the logits say."""
    for res, _ in _readings(lm_cell(), monkeypatch, {"kv_dtype": "int8"}):
        assert res["correct"] is False
        assert res["check"]["kv_bits_short"]["value"] == pytest.approx(
            8.0, abs=0.01)


def test_cnn_control_fails_the_exact_comparison(monkeypatch):
    for res, control in _readings(cnn_cell(), monkeypatch):
        assert res["correct"] is True
        assert res["check"]["mismatched_answers"]["value"] == 0
        assert control > 0
