"""The LM step picks greedy tokens on the device
(`repro.serve.runtime.adapters.LMDecodeAdapter.step`).

* over several steps at ragged positions, the step's (B, 1) int32
  tokens equal ``np.argmax`` of ``model.decode``'s last-position logits,
  and
  the cache it carries is the plain decode's;
* a step that fed a sampled cursor copies the full float32 rows, whose
  argmax is the device's token;
* meshless and dp=4 schedulers (ragged slots, ragged prompts) serve the
  same tokens, greedy and sampled (the rows gathered from the mesh);
* a greedy step copies ``4 x phys_slots`` bytes and counts no host
  sampling step; a sampled serve counts every step it takes and replays
  across runs and policies;
* the step is one program, ``jit_decode``, whose first output is the
  (B,) int32 tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.qwen2p5_3b import smoke_config
from repro.models.api import build
from repro.nn.layers import padded_vocab
from repro.obs import trace as obs
from repro.parallel.ctx import make_mesh
from repro.serve.runtime import LMDecodeAdapter, Request, Scheduler

MAX_LEN = 32


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def lm():
    cfg = smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _adapter(lm, mesh=None):
    _, model, params = lm
    return LMDecodeAdapter(model, params, max_len=MAX_LEN, mesh=mesh)


def _ragged(n=5, max_new=4):
    """Prompts of 1 to n tokens, so slots prefill and decode side by
    side."""
    return [Request(prompt=np.arange(2, 3 + i, dtype=np.int32) * (i + 1),
                    max_new_tokens=max_new + i % 2) for i in range(n)]


def _outs(reqs):
    return [r.out.tolist() for r in reqs]


def _dp4_mesh():
    """Four data-parallel devices and no model axis: a model axis splits
    the head's reductions, which moves the logits' rounding (greedy
    tokens hold, `test_runtime.py::test_dp_sharded_parity`; sampled
    draws need the same rows bit for bit)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    return make_mesh((4, 1), ("data", "model"), devices=jax.devices()[:4])


def test_greedy_tokens_are_the_argmax_of_decode_logits(lm):
    _, model, params = lm
    adapter = _adapter(lm)
    plain = jax.jit(model.decode)
    b = 4
    cache = ref_cache = adapter.init_state(b)
    pos = np.array([0, 3, 7, 12], np.int32)     # ragged positions
    tok = np.array([[5], [9], [2], [17]], np.int32)
    for _ in range(6):
        rows, cache = adapter.step(cache, tok, pos)
        logits, ref_cache = plain(params, ref_cache, jnp.asarray(tok),
                                  jnp.asarray(pos))
        want = np.argmax(np.asarray(logits[:, -1], np.float32), -1)
        assert rows.dtype == np.int32 and rows.shape == (b, 1)
        assert np.array_equal(rows[:, 0], want)
        tok, pos = rows, pos + 1
    for got, ref in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
        assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_a_sampled_feed_copies_full_rows_whose_argmax_is_the_token(lm):
    cfg, _, _ = lm
    adapter = _adapter(lm)
    b = 3
    tok = np.array([[4], [8], [15]], np.int32)
    pos = np.array([0, 2, 5], np.int32)
    toks, _ = adapter.step(adapter.init_state(b), tok, pos)
    sampled = adapter.begin(Request(prompt=np.array([4], np.int32)),
                            rid=0, greedy=False, seed=3)
    adapter.feed(sampled)
    rows, _ = adapter.step(adapter.init_state(b), tok, pos)
    assert rows.dtype == np.float32
    assert rows.shape == (b, padded_vocab(cfg.vocab))
    assert np.array_equal(rows.argmax(-1), toks[:, 0])
    # a greedy cursor consuming either row emits the same token
    for row in (toks[1], rows[1]):
        cur = adapter.begin(Request(prompt=np.array([4], np.int32),
                                    max_new_tokens=2), rid=1)
        adapter.consume(cur, row)
        assert cur.out == [int(toks[1, 0])]
    # the mark lasts one step
    again, _ = adapter.step(adapter.init_state(b), tok, pos)
    assert again.dtype == np.int32


@pytest.mark.parametrize("greedy", [True, False])
def test_dp4_ragged_matches_meshless(lm, greedy):
    mesh = _dp4_mesh()
    want = Scheduler(_adapter(lm), 3).serve(_ragged(), greedy=greedy,
                                            seed=11)
    sched = Scheduler(_adapter(lm, mesh=mesh), 3, mesh=mesh)
    got = sched.serve(_ragged(), greedy=greedy, seed=11)
    assert sched.slots.phys == 4
    assert _outs(got) == _outs(want)


@pytest.mark.parametrize("dp", [1, 4])
def test_greedy_step_copies_four_bytes_a_slot(lm, dp):
    mesh = _dp4_mesh() if dp == 4 else None
    sched = Scheduler(_adapter(lm, mesh=mesh), 3, mesh=mesh)
    obs.enable()
    sched.serve(_ragged())
    steps = len(sched.step_log)
    counters = obs.counter_values()
    assert steps > 0
    assert counters["lm.bytes_to_host"] == steps * 4 * sched.slots.phys
    assert counters.get("lm.host_sample_steps", 0) == 0


def test_sampled_serve_takes_the_host_path_and_replays(lm):
    cfg, _, _ = lm
    mk = lambda: _ragged(4, max_new=6)
    adapter = _adapter(lm)
    obs.enable()
    sched = Scheduler(adapter, 2)
    a = sched.serve(mk(), greedy=False, seed=7)
    steps = len(sched.step_log)
    counters = obs.counter_values()
    assert counters["lm.host_sample_steps"] == steps
    # the rows cross in the logits' own dtype
    last = adapter._decode.lower(
        adapter.params, adapter.init_state(2), jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32)).out_info[1]
    assert counters["lm.bytes_to_host"] == \
        steps * 2 * padded_vocab(cfg.vocab) * last.dtype.itemsize
    obs.disable()
    b = Scheduler(_adapter(lm), 2).serve(mk(), greedy=False, seed=7)
    c = Scheduler(_adapter(lm), 3, policy="wave").serve(
        mk(), greedy=False, seed=7)
    assert _outs(a) == _outs(b) == _outs(c)


def test_step_is_one_program_with_int32_tokens_first(lm):
    adapter = _adapter(lm)
    b = 2
    lowered = adapter._decode.lower(
        adapter.params, adapter.init_state(b), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b,), jnp.int32))
    text = lowered.as_text()
    assert text.count("module @jit_decode") == 1
    assert text.count("module @") == 1
    first = jax.tree.leaves(lowered.out_info)[0]
    assert first.shape == (b,) and first.dtype == jnp.int32
