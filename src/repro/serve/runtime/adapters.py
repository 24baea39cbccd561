"""Pluggable workload adapters for the serving runtime.

`WorkloadAdapter` is the contract between the scheduler (admission,
slots, stats — workload-agnostic) and a workload (what a request *is*
and what one engine step computes). An adapter provides:

* **cache spec** — ``init_state(phys_slots)`` builds the batched decode
  state (slot-major), ``place_state`` shards it over the mesh, and
  ``state_reset_keys`` names the per-slot *carried* state subtrees that
  must be cleared when a slot is re-admitted (SSM / RG-LRU recurrent
  rows; positional KV needs no clear — a fresh request's mask only ever
  admits positions it has itself written).
* **prefill/step** — ``step(state, feed, positions)`` runs one engine
  step over all physical slots and returns per-slot host outputs. The
  runtime is token-synchronous: LM prefill is the same step fed prompt
  tokens (exactly what the wave engine's replay prefill lowered to), so
  one jitted callable serves both phases at one compiled shape.
* **request cursor** — ``begin`` wraps a payload into a cursor,
  ``feed``/``consume`` drive it one step at a time, and ``consume``'s
  return value is the **finished predicate** (mid-wave eviction point).

With `repro.obs` on, the LM and vision steps split into three spans
each (``lm.*`` / ``vision.*``): ``dispatch`` (inputs to the device and
the jitted call returning), ``device_wait`` (blocking on the step's
outputs) and ``logits_to_host`` (the copy of the step's host outputs:
for the LM, one greedy int32 token per slot, or the last position's
full logits in a step that feeds a sampled request). The LM also counts
``lm.bytes_to_host`` and ``lm.host_sample_steps``, the steps that
copied full logits. A hybrid MoE LM's step also returns, after the
tokens and in the same copy, how many experts each MoE layer's tokens
picked, which the adapter adds to ``moe.experts_hit``. Clearing a
re-admitted slot's carried state (SSM, RG-LRU or conv rows) is the span
``lm.state_reset``, inside the scheduler's ``serve.admit``. Off, the step
does what it did unspanned: the copy itself blocks on the program. On,
the device runs the same work in the same order: everything is queued
before the wait.

Per-request bit-exactness invariant: every adapter's step must be
row-independent (slot *i*'s outputs depend only on slot *i*'s feeds),
which is what makes continuous batching bit-exact vs synchronous waves
regardless of admission order. The vector-position decode path
(`repro.nn.attention.attn_decode`) preserves this by construction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import numpy as np

from repro.obs import trace as obs

# per-slot carried state that must be cleared on slot reuse, keyed by the
# cache subtree name: leaves are (layers, slots, ...) with zero init
# (SSM, RG-LRU and short-conv rows)
STATE_RESET_KEYS = ("ssm", "rec", "conv")


@dataclasses.dataclass
class Request:
    """One LM generation request (public serving API; re-exported by
    `repro.serve.engine` for compatibility)."""
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 32
    out: Optional[np.ndarray] = None


class WorkloadAdapter:
    """Base contract; see module docstring. Subclasses set ``name``,
    ``max_len`` and implement the hooks below."""

    name: str = "?"
    max_len: int = 1

    # ---- cache spec ----
    def init_state(self, phys_slots: int):
        return None

    def place_state(self, state, mesh, dp_axis):
        return state

    def reset_state(self, state, slot_mask: np.ndarray):
        """Clear carried per-slot state for slots where mask is True."""
        return state

    # ---- engine step ----
    def input_spec(self) -> Tuple[Tuple[int, ...], Any]:
        """(per-slot feed shape, dtype) for the scheduler's feed buffer."""
        raise NotImplementedError

    def step(self, state, feed: np.ndarray, positions: np.ndarray):
        """One step over all phys slots -> (per-slot host outputs, state)."""
        raise NotImplementedError

    # ---- request cursor ----
    def begin(self, payload, *, rid: int, greedy: bool = True,
              seed: int = 0):
        """Payload -> cursor. cursor.done may already be True (e.g.
        max_new_tokens == 0): such requests complete without ever
        occupying a slot."""
        raise NotImplementedError

    def feed(self, cursor) -> Tuple[np.ndarray, int]:
        """Next (input row, cache position) for this cursor's slot."""
        raise NotImplementedError

    def consume(self, cursor, row) -> bool:
        """Fold one step's output row into the cursor; True == finished
        (the scheduler evicts the slot and admits the next request)."""
        raise NotImplementedError

    def finish(self, cursor):
        """Attach final outputs to the payload (called exactly once)."""

    def result(self, cursor):
        """The per-request output object `Scheduler.serve` returns."""
        return cursor.payload

    def reserve_tokens(self, cursor) -> int:
        """Worst-case cache positions for page reservation."""
        return self.max_len

    def prompt_len(self, cursor) -> int:
        """Cache positions the request needs just to be admitted."""
        return 1

    def tokens_out(self, cursor) -> int:
        return 0


# ------------------------------------------------------------- LM decode ---

@dataclasses.dataclass
class _LMCursor:
    payload: Request
    rid: int
    prompt: np.ndarray
    max_new: int
    greedy: bool
    rng: Optional[np.random.Generator]
    next_pos: int = 0               # next cache position to feed
    pending: int = 0                # last sampled token, fed next
    out: Optional[List[int]] = None
    done: bool = False


class LMDecodeAdapter(WorkloadAdapter):
    """Token-synchronous LM decode over the Model API.

    Prefill and decode are the same jitted ``decode`` call
    (``model.decode`` and the greedy pick below) with a per-slot position
    vector: a slot working through its prompt is fed prompt tokens
    (outputs ignored until the last prompt position — the wave engine's
    replay-prefill, now per slot), then generated tokens.
    An all-equal position vector is bit-exact vs the scalar-index wave
    path, so per-request outputs are identical to `Engine.generate`'s.

    Per-request semantics (cohort-independent, unlike the old ragged
    wave prefill which let a short prompt attend to pad tokens): output
    k exists iff ``k < max_new_tokens`` and ``prompt_len + k < max_len``
    and no earlier EOS; the EOS token itself is emitted (wave parity).
    Non-greedy sampling draws from a per-request generator seeded
    ``(seed, rid)`` so outputs stay admission-order invariant.

    The jitted step picks each slot's greedy token on the device (the
    lowest index among the largest logits, as ``np.argmax`` picks), so a
    step copies one int32 per slot to the host, and for a hybrid MoE LM
    one more per MoE layer, its experts hit. A step that feeds a
    sampled cursor (``feed`` marks it) copies the last position's full
    logits as well, and its greedy cursors take the argmax of their row:
    the same token.

    ``donate_state`` donates the step's input cache to the program, which
    then updates it in place: one cache on the device instead of the
    input's and the output's (the scheduler drops the old state when the
    step returns). Undonated, XLA copies a cache that the step updates in
    place into a fresh buffer every step: for large caches, bytes and
    time of their own.
    """

    name = "lm"

    def __init__(self, model, params, max_len: int, *, eos_id: int = 1,
                 mesh=None, dp_axis: str = "data", plan=None,
                 donate_state: bool = False):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.max_len = max_len
        self.eos = eos_id
        self.plan = plan
        self.mesh = mesh
        self.dp_axis = dp_axis
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            params = jax.device_put(params, NamedSharding(mesh, P()))
        self.params = params
        self._host_rows = False     # a sampled cursor was fed this step
        self._n_hits = model.experts_hit_layers

        def decode(params, cache, token, index):
            logits, cache, *hits = model.decode(params, cache, token, index)
            last = logits[:, -1]                                 # (B, V)
            picks = jnp.argmax(last, -1).astype(jnp.int32)
            if hits:
                picks = jnp.concatenate([picks, hits[0]])
            return picks, last, cache

        self._decode = jax.jit(decode,
                               donate_argnums=(1,) if donate_state else ())

    # ---- placement (same layout as the wave engine) ----

    def _put_wave(self, arr):
        import jax
        import jax.numpy as jnp

        if self.mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.parallel.sharding import axis_entry
        spec = P(axis_entry(self.mesh, self.dp_axis),
                 *([None] * (np.ndim(arr) - 1)))
        return jax.device_put(jnp.asarray(arr),
                              NamedSharding(self.mesh, spec))

    def init_state(self, phys_slots: int):
        cache = self.model.init_cache(phys_slots, self.max_len)
        return self.place_state(cache, self.mesh, self.dp_axis)

    def place_state(self, cache, mesh, dp_axis):
        if mesh is None:
            return cache
        import jax

        from repro.parallel.sharding import cache_shardings
        return jax.device_put(cache, cache_shardings(cache, mesh))

    def reset_state(self, cache, slot_mask: np.ndarray):
        """Zero carried recurrent rows (SSM / RG-LRU) for re-admitted
        slots; positional KV subtrees are left alone — the causal mask
        only admits positions the new request has itself written."""
        keys = [k for k in STATE_RESET_KEYS if k in cache]
        if not keys:
            return cache
        import jax
        import jax.numpy as jnp

        with obs.span("lm.state_reset", cat="lm"):
            mask = jnp.asarray(slot_mask)

            def clear(leaf):
                m = mask.reshape((1, mask.shape[0]) + (1,) * (leaf.ndim - 2))
                return jnp.where(m, jnp.zeros_like(leaf), leaf)

            out = dict(cache)
            for k in keys:
                out[k] = jax.tree.map(clear, cache[k])
            return self.place_state(out, self.mesh, self.dp_axis)

    # ---- engine step ----

    def input_spec(self):
        return ((1,), np.int32)

    def step(self, cache, feed, positions):
        """-> (rows, cache). A row is the slot's greedy token, shaped
        like its feed row ((B, 1) int32), or, in a step that fed a
        sampled cursor, the last position's logits ((B, V) float32)."""
        with obs.span("lm.dispatch", cat="lm"):
            picks, last, cache = self._decode(
                self.params, cache, self._put_wave(feed),
                self._put_wave(positions.astype(np.int32)))
        host_rows, self._host_rows = self._host_rows, False
        n = self._n_hits
        out = ((last, picks) if n else (last,)) if host_rows else (picks,)
        with obs.span("lm.device_wait", cat="lm") as sp:
            sp.sync((out, cache))
        with obs.span("lm.logits_to_host", cat="lm"):
            got = [np.asarray(o) for o in out]
        obs.counter("lm.bytes_to_host").add(sum(g.nbytes for g in got))
        rows = got[0]
        if n:
            obs.counter("moe.experts_hit").add(int(got[-1][-n:].sum()))
            if not host_rows:
                rows = rows[:-n]
        if not host_rows:
            return rows[:, None], cache
        obs.counter("lm.host_sample_steps").add(1)
        return rows.astype(np.float32), cache   # exact widening

    # ---- request cursor ----

    def begin(self, payload: Request, *, rid: int, greedy: bool = True,
              seed: int = 0):
        prompt = np.asarray(payload.prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            # zero-length prompt: pad to a single BOS(=0) token, the
            # wave engines' filler convention
            prompt = np.zeros((1,), np.int32)
        max_new = int(payload.max_new_tokens)
        cur = _LMCursor(
            payload=payload, rid=rid, prompt=prompt, max_new=max_new,
            greedy=greedy,
            rng=None if greedy else np.random.default_rng((seed, rid)),
            out=[])
        if max_new <= 0:
            cur.done = True        # completes without occupying a slot
        return cur

    def reserve_tokens(self, cur: _LMCursor) -> int:
        return len(cur.prompt) + cur.max_new

    def prompt_len(self, cur: _LMCursor) -> int:
        return len(cur.prompt)

    def feed(self, cur: _LMCursor):
        if not cur.greedy:
            self._host_rows = True  # the next step copies full logits
        p = cur.next_pos
        tok = cur.prompt[p] if p < len(cur.prompt) else cur.pending
        return np.asarray([tok], np.int32), p

    def _sample(self, cur: _LMCursor, row: np.ndarray) -> int:
        if cur.greedy:
            if np.issubdtype(row.dtype, np.integer):
                return int(row[0])      # picked on the device
            return int(row.argmax(-1))
        p = np.exp(row - row.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return int(cur.rng.choice(row.shape[-1], p=p))

    def consume(self, cur: _LMCursor, row: np.ndarray) -> bool:
        q = cur.next_pos            # the position just fed
        cur.next_pos = q + 1
        if q < len(cur.prompt) - 1:
            return False            # still prefilling: output ignored
        # output k = q - (P-1); emit iff k < max_new and P + k < max_len
        if len(cur.out) < cur.max_new and cur.next_pos < self.max_len:
            nxt = self._sample(cur, row)
            cur.out.append(nxt)
            cur.pending = nxt
            if (nxt == self.eos or len(cur.out) >= cur.max_new
                    or cur.next_pos + 1 >= self.max_len):
                cur.done = True
        else:
            cur.done = True         # no room left for another token
        return cur.done

    def finish(self, cur: _LMCursor):
        cur.payload.out = np.array(cur.out, np.int32)

    def tokens_out(self, cur: _LMCursor) -> int:
        return len(cur.out)


# ---------------------------------------------------------------- vision ---

@dataclasses.dataclass
class _VisionCursor:
    payload: np.ndarray             # float32 image (H, W, C)
    rid: int
    out: Optional[np.ndarray] = None
    done: bool = False


class VisionAdapter(WorkloadAdapter):
    """Stateless quantized-CNN classification: a request is one image,
    one engine step is one batched integer forward, and every admitted
    request finishes after exactly one step (admission is the only
    scheduling decision, so continuous batching == don't wait for a full
    wave). `begin` keeps the float32 image as it came and touches no
    device; the step's one jitted program quantizes the whole feed with
    the net's input spec (one elementwise op beside the convs, on each
    chip's shard under ``mesh=``), then runs the integer forward. Pad
    slots are zeros, which quantize to 0.
    """

    name = "vision"
    max_len = 1

    def __init__(self, qnet, *, mesh=None, dp_axis: str = "data",
                 backend: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        from repro.core.quantize import quantize
        from repro.vision.models import forward_int

        self.qnet = qnet
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.backend = backend

        def vision_forward(x, eps):
            x_hat = quantize(x, qnet.input_spec, eps=eps)
            return forward_int(qnet, x_hat, backend=backend, mesh=mesh)

        self._forward = jax.jit(vision_forward)
        # the input grid as an operand: the program divides by it exactly
        # as an eager `quantize_input` does (see `quantize`)
        self._eps = jnp.float32(qnet.input_spec.eps)
        self._spec = ((*qnet.cfg.in_hw, qnet.cfg.in_ch), np.float32)

    def input_spec(self):
        return self._spec

    def step(self, state, feed, positions):
        import jax.numpy as jnp

        with obs.span("vision.dispatch", cat="vision"):
            logits = self._forward(jnp.asarray(feed), self._eps)
        with obs.span("vision.device_wait", cat="vision") as sp:
            sp.sync(logits)
        with obs.span("vision.logits_to_host", cat="vision"):
            rows = np.asarray(logits)
        return rows, state

    def begin(self, payload, *, rid: int, greedy: bool = True,
              seed: int = 0):
        return _VisionCursor(payload=np.asarray(payload, np.float32),
                             rid=rid)

    def reserve_tokens(self, cur) -> int:
        return 1

    def prompt_len(self, cur) -> int:
        return 1

    def feed(self, cur: _VisionCursor):
        return cur.payload, 0

    def consume(self, cur: _VisionCursor, row) -> bool:
        cur.out = np.asarray(row)
        cur.done = True
        return True

    def result(self, cur: _VisionCursor):
        return cur.out

    def tokens_out(self, cur: _VisionCursor) -> int:
        return 1
