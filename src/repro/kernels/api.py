"""Unified quantized-op backend API: one registry, one entry point per op.

The paper's core contribution is *flexible* dispatch of sub-byte SIMD
dot-product kernels across precisions; PULP-NN makes that usable with a
kernel-library API where one entry point per op selects the backend. This
module is that layer for the TPU repro. Backends register under
``(op, name)`` for the ops ``qdot`` (packed sub-byte GEMM, eq. 2-4) and
``qconv`` (fused implicit-GEMM conv), each exposing

    supports(shape, a_bits, w_bits, platform) -> bool
    run(params, x, *, epilogue, scale, block) -> array

Registered backends:

  pallas            real Mosaic/TPU Pallas kernel (asserts a TPU platform —
                    no production call site can silently fall into
                    interpret mode again)
  pallas_interpret  the same kernel under the Pallas interpreter: the
                    correctness/tests/dry-run backend, selected explicitly
  xla               XLA-native unpack + int dot_general + fused epilogue —
                    the production lowering off-TPU and for shapes the
                    kernels reject
  eager_ref         the independent numpy oracles (tests/debugging)

Resolution order for the per-call backend: explicit ``backend=`` argument
-> ``REPRO_QBACKEND`` env override -> capability-ordered default
(``pallas`` where supported, i.e. on TPU, else ``xla``). Block shapes come
from the per-(shape, bits, backend) autotune cache (`repro.kernels.tune`),
falling back to the analytic `default_block`/`conv_default_block`.

**Pipeline modes (Mac&Load analogue).** The pallas-family backends take a
``pipeline`` mode (`repro.kernels.common.PIPELINE_MODES`): ``off`` leans
on the grid pipeliner, ``double_buffer`` issues manual two-slot DMA
prefetch so the next K tile's (qdot) / receptive-field tap's (qconv) copy
overlaps the current tile's unpack+dot. Resolution order: explicit
``pipeline=`` argument (or plan hint / plan-rule field) ->
``REPRO_QPIPELINE`` env override -> the measured autotune-cache winner
for this (op, shape, bits, backend) -> ``off``. The ``xla`` and
``eager_ref`` backends have no pipeline concept and ignore the mode, so
differential tests can force one mode suite-wide.

**Observability.** With ``REPRO_OBS=1`` (`repro.obs`), every resolution
records one structured dispatch event — requested backend/pipeline, plan
hint, env override, tune-cache hit/miss and winner, final choice with
per-field provenance — queryable via `repro.obs.dispatch_log()`, and
every eager entry-point call bumps the per-(op, bits, backend, pipeline)
MAC/byte counters and runs inside a ``cat='kernel'`` span. A call under
a `jit` trace records neither: there it would time the trace and count
once per compilation. Disabled (the default), the instrumentation is a
single predicate per call.

**Cluster-parallel path (paper fig. 9).** Passing ``mesh=`` to
`qdot`/`qconv` (or calling `qdot_sharded`/`qconv_sharded` directly) runs
the op under `shard_map` on an N-device mesh — the JAX analog of the
paper's N-core PULP cluster. Packed weights are tensor-parallel over the
output-feature axis (each device owns a disjoint Cout slice, like a
cluster core writing its own output-channel group into TCDM), activations
are data-parallel over the batch axis. Because K stays unsharded, each
shard's int32 accumulation is complete and the eq. 3/4 epilogue (all
per-output-channel parameters) runs locally — the sharded path needs **no
psum** and is bit-exact vs the single-device backends. The inner backend
is resolved per *local shard shape* by the same registry rules;
``eager_ref`` is host-side numpy and is rejected under `shard_map`.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.quantize import SegmentedLinearParams
from repro.kernels import tune
from repro.kernels.common import (PIPELINE_MODES, apply_epilogue,
                                  check_pipeline, round_up)
from repro.obs import counters as obs_counters
from repro.obs import env as obsenv
from repro.obs import trace as obs

# "qdot_mixed" is the fine-grain mixed-precision GEMM (segmented weight
# containers, per-tile unpack width — Nadalini et al. 2307.01056); qdot
# routes into it when params is a SegmentedLinearParams.
OPS = ("qdot", "qdot_mixed", "qconv")
ENV_VAR = "REPRO_QBACKEND"
ENV_PIPELINE = "REPRO_QPIPELINE"
# capability-ordered default resolution; backends not listed here (the
# interpreter, the numpy oracle) are only ever selected explicitly
DEFAULT_ORDER: Tuple[str, ...] = ("pallas", "xla")


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    op: str
    name: str
    supports: Callable  # (shape, a_bits, w_bits, platform) -> bool
    run: Callable       # (params, x, *, epilogue, scale, block, pipeline)
    doc: str = ""


_REGISTRY: Dict[Tuple[str, str], BackendSpec] = {}


def register(op: str, name: str, *, supports: Callable, run: Callable,
             doc: str = "", override: bool = False) -> BackendSpec:
    """Register a backend for ``op``; later kernels (fused-load qdot, GPU,
    2-bit crumb paths) add themselves here instead of another boolean.
    Re-registering an existing (op, name) raises unless ``override=True``
    — silent replacement of a production backend is never an accident
    worth allowing."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; ops: {OPS}")
    if not override and (op, name) in _REGISTRY:
        raise ValueError(
            f"backend {name!r} already registered for op {op!r}; pass "
            "override=True to replace it")
    spec = BackendSpec(op=op, name=name, supports=supports, run=run, doc=doc)
    _REGISTRY[(op, name)] = spec
    return spec


def backends(op: str) -> Tuple[str, ...]:
    """Registered backend names for ``op`` (sorted)."""
    return tuple(sorted(n for (o, n) in _REGISTRY if o == op))


def get(op: str, name: str) -> BackendSpec:
    spec = _REGISTRY.get((op, name))
    if spec is None:
        raise KeyError(
            f"no backend {name!r} registered for op {op!r}; "
            f"available: {list(backends(op))}")
    return spec


def platform() -> str:
    return jax.default_backend()


def resolve(op: str, shape, a_bits: int, w_bits: int, *,
            backend: Optional[str] = None) -> BackendSpec:
    """Pick the backend for one call.

    Explicit ``backend`` -> ``REPRO_QBACKEND`` env override ->
    capability-ordered default (first DEFAULT_ORDER entry whose
    ``supports`` accepts this shape/bits/platform).
    """
    requested = backend or obsenv.get(ENV_VAR) or None
    if requested:
        return get(op, requested)
    plat = platform()
    for name in DEFAULT_ORDER:
        spec = _REGISTRY.get((op, name))
        if spec is not None and spec.supports(shape, a_bits, w_bits, plat):
            return spec
    raise RuntimeError(
        f"no default backend supports op {op!r} shape {shape} "
        f"A{a_bits}W{w_bits} on {plat!r}; registered: {list(backends(op))}")


def default_backend(op: str, shape=None, a_bits: int = 8,
                    w_bits: int = 8) -> str:
    """Name the default resolution would pick (diagnostics/banners)."""
    if shape is None:
        shape = ((256, 1024, 1024) if op == "qdot"
                 else (1, 16, 16, 32, 3, 3, 1, 1, 64, 1))
    return resolve(op, shape, a_bits, w_bits).name


def registry_table() -> Tuple[Tuple[str, str, str], ...]:
    """(op, backend, doc) rows for docs/CLIs."""
    return tuple((op, name, _REGISTRY[(op, name)].doc)
                 for (op, name) in sorted(_REGISTRY))


def resolve_legacy_backend(backend: Optional[str],
                           use_kernel: Optional[bool],
                           interpret: Optional[bool]) -> Optional[str]:
    """Deprecation shim shared by the op compat wrappers
    (`qlinear_apply`, `qconv2d_apply`): map the pre-registry
    ``use_kernel``/``interpret`` booleans onto a backend name.

    True -> 'pallas_interpret' (the old default silently ran interpret
    mode), True + interpret=False -> 'pallas', False -> 'xla'. Passing
    both the new ``backend`` and a deprecated boolean is contradictory
    and raises.
    """
    if use_kernel is None and interpret is None:
        return backend
    if backend is not None:
        raise ValueError(
            "pass either backend= or the deprecated use_kernel=/"
            "interpret= booleans, not both")
    warnings.warn(
        "use_kernel=/interpret= are deprecated; pass backend="
        "'pallas'|'pallas_interpret'|'xla'|'eager_ref' instead "
        "(see repro.kernels.api)", DeprecationWarning, stacklevel=3)
    uk = True if use_kernel is None else use_kernel
    if not uk:
        return "xla"
    return "pallas" if interpret is False else "pallas_interpret"


# ------------------------------------------------------- shared XLA core ---

def xla_int_gemm(x_q, w_packed, *, w_bits: int, kappa=None, lam=None,
                 m_mul=None, d: int = 0, out_bits: int = 8,
                 epilogue: str = "int", scale=1.0, out_dtype=None):
    """The one shared XLA int-GEMM + epilogue implementation.

    x_q: (..., K_pad) int8 integer images (already on the a_bits grid);
    w_packed: (K_pad/pf_w, N) chunk-planar packed weights. Unpack lowers to
    XLA convert ops the TPU compiler fuses into the int dot. ``scale`` may
    be a scalar or per-channel (N,) array (dequant epilogue). Used by the
    ``xla`` qdot backend and by the nn dense int path — previously two
    divergent copies (`qmatmul_jnp` vs `nn/layers._int_matmul`).
    """
    w = packing.unpack(w_packed, w_bits, True, axis=0)
    acc = jax.lax.dot_general(
        x_q, w, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    if out_dtype is None:
        out_dtype = {"int": jnp.int8, "dequant": jnp.bfloat16,
                     "raw": jnp.int32}[epilogue]
    return apply_epilogue(acc, kappa, lam, m_mul, d=d, out_bits=out_bits,
                          epilogue=epilogue, scale=scale,
                          out_dtype=out_dtype)


def xla_grouped_int_gemm(x_q, w_packed, *, w_bits: int, scale,
                         out_dtype=jnp.bfloat16):
    """`xla_int_gemm` over a group of weight matrices held side by side,
    the experts of an MoE layer: one batched int dot, dequant epilogue.

    x_q: (G, M, K_pad) int8, row block g meets matrix g; w_packed:
    (G, K_pad/pf_w, N) chunk-planar packed along K, unpacked by the same
    rule as a dense layer's; scale: (G, N) per-channel dequant scales.
    Returns (G, M, N) ``out_dtype``.
    """
    w = packing.unpack(w_packed, w_bits, True, axis=1)
    acc = jax.lax.dot_general(x_q, w, (((2,), (1,)), ((0,), (0,))),
                              preferred_element_type=jnp.int32)
    return apply_epilogue(acc, None, None, None, d=0, out_bits=8,
                          epilogue="dequant", scale=scale[:, None, :],
                          out_dtype=out_dtype)


# ------------------------------------------------------------ qdot entry ---

def _flatten_lead(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _pad_axis(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _resolve_call(op: str, shape, a_bits: int, w_bits: int, *,
                  backend: Optional[str], block: Optional[tuple],
                  pipeline: Optional[str], plan_hints: Optional[dict],
                  sharded: bool = False):
    """One-stop per-call resolution: merge plan hints, resolve the
    backend (explicit -> plan -> ``REPRO_QBACKEND`` -> capability
    default), look up the tuned (block, pipeline) with one cache probe,
    and — when observability is on — record the full decision with
    provenance in the dispatch log (`repro.obs.dispatch_log`).

    Pipeline: explicit -> plan -> ``REPRO_QPIPELINE`` -> tuned winner ->
    'off'. Block: explicit -> plan -> tuned winner -> None (the backend's
    analytic selector). Returns ``(spec, block, pipeline)``.
    """
    hints = plan_hints or {}
    explicit_backend, explicit_block = backend, block
    explicit_pipeline = pipeline
    backend = backend or hints.get("backend")
    block = block or hints.get("block")
    pipeline = pipeline or hints.get("pipeline")

    env_backend = obsenv.get(ENV_VAR) or None
    spec = resolve(op, shape, a_bits, w_bits, backend=backend)
    if sharded:
        _reject_host_backend(spec)
    entry = tune.get_entry(op, shape, a_bits, w_bits, spec.name)

    block_source = ("explicit" if explicit_block is not None
                    else "plan" if block is not None
                    else "tuned" if entry is not None else "analytic")
    if block is None and entry is not None:
        block = tuple(entry["block"])

    # env is only consulted (and therefore only validated) when nothing
    # higher-precedence decided — an explicit arg or plan hint must
    # shadow even a bogus REPRO_QPIPELINE value
    env_pipeline = (None if pipeline is not None
                    else obsenv.get(ENV_PIPELINE) or None)
    pipeline_source = ("explicit" if explicit_pipeline is not None
                      else "plan" if pipeline is not None
                      else "env" if env_pipeline is not None
                      else "tuned" if entry is not None else "default")
    pipeline = check_pipeline(
        pipeline or env_pipeline
        or (entry["pipeline"] if entry is not None else None) or "off")

    if obs.enabled():
        backend_source = ("explicit" if explicit_backend is not None
                          else "plan" if backend is not None
                          else "env" if env_backend is not None
                          else "default")
        obs.dispatch_event(
            op=op, shape=tuple(int(s) for s in shape),
            a_bits=int(a_bits), w_bits=int(w_bits),
            backend=spec.name, backend_source=backend_source,
            plan_backend=hints.get("backend"), env_backend=env_backend,
            block=None if block is None else tuple(int(b) for b in block),
            block_source=block_source,
            pipeline=pipeline, pipeline_source=pipeline_source,
            env_pipeline=env_pipeline,
            tune_cache_hit=entry is not None,
            tune_winner=None if entry is None else {
                "block": list(entry["block"]),
                "pipeline": entry["pipeline"], "us": entry["us"]},
            sharded=sharded)
    return spec, block, pipeline


def _traced(tree) -> bool:
    """True when any leaf of ``tree`` is a tracer: the call is being
    staged into a `jit` (or other) trace, not run."""
    return any(isinstance(v, jax.core.Tracer)
               for v in jax.tree_util.tree_leaves(tree))


def _run_counted(spec, op: str, shape, a_bits: int, w_bits: int,
                 pipeline: str, thunk, operands=(),
                 w_packed_bytes: Optional[int] = None):
    """Run the resolved backend. With observability on and the call
    eager, bump the (op, bits, backend, pipeline) MAC/byte counters and
    wrap the run in a ``cat='kernel'`` span that blocks on the result so
    device time lands inside it; off, or with any of ``operands`` a
    tracer, it's a bare call. ``w_packed_bytes`` overrides the
    uniform-container weight-byte estimate (segmented containers stream
    fewer bytes than a uniform buffer at the widest width)."""
    if not obs.enabled() or _traced(operands):
        return thunk()
    costs = obs_counters.record(op, shape, a_bits, w_bits,
                                backend=spec.name, pipeline=pipeline,
                                w_packed_bytes=w_packed_bytes)
    with obs.span(op, cat="kernel", backend=spec.name, pipeline=pipeline,
                  a_bits=int(a_bits), w_bits=int(w_bits),
                  shape=tuple(int(s) for s in shape),
                  macs=costs["macs"],
                  packed_bytes=costs["packed_bytes"]) as sp:
        return sp.sync(thunk())


def qdot(params, x_hat, *, epilogue: str = "int", scale=1.0,
         backend: Optional[str] = None, block: Optional[tuple] = None,
         pipeline: Optional[str] = None,
         plan_hints: Optional[dict] = None, mesh=None,
         dp_axis: str = "data", tp_axis: str = "model"):
    """Quantized dot: integer-image activations x packed weights.

    params: `QuantizedLinearParams` — or `SegmentedLinearParams`, which
    routes through the mixed-operand op ``qdot_mixed`` (per-segment
    weight widths, same backend names). x_hat: (..., K_logical) int8
    integer images (unpacked); padded to CHUNK and packed on the fly.
    Leading dims are flattened for the GEMM and restored on the output.
    With ``mesh=`` the call routes through `qdot_sharded`
    (cluster-parallel execution). ``pipeline`` selects the kernel
    execution mode (module docstring).
    """
    if mesh is not None:
        if isinstance(params, SegmentedLinearParams):
            raise NotImplementedError(
                "qdot(mesh=...) does not take SegmentedLinearParams yet: "
                "segment boundaries and the TP output-feature split would "
                "have to be co-aligned; shard per segment above the "
                "registry instead")
        return qdot_sharded(params, x_hat, mesh=mesh, dp_axis=dp_axis,
                            tp_axis=tp_axis, epilogue=epilogue, scale=scale,
                            backend=backend, block=block, pipeline=pipeline,
                            plan_hints=plan_hints)
    x2, lead = _flatten_lead(x_hat)
    x2 = packing.pad_to_chunk(x2, axis=-1)
    xp = packing.pack(x2, params.a_bits, axis=-1)
    out = qdot_packed(params, xp, epilogue=epilogue, scale=scale,
                      backend=backend, block=block, pipeline=pipeline,
                      plan_hints=plan_hints)
    return out.reshape(*lead, out.shape[-1])


def qdot_packed(params, x_packed, *, epilogue: str = "int", scale=1.0,
                backend: Optional[str] = None,
                block: Optional[tuple] = None,
                pipeline: Optional[str] = None,
                plan_hints: Optional[dict] = None):
    """`qdot` over already-packed activations (fused chains where the
    previous layer's epilogue emitted packed integer images).

    `SegmentedLinearParams` dispatches to the ``qdot_mixed`` registry op:
    same backend names, but the pallas kernel switches unpack width per
    N tile and the xla/eager backends loop segments. The resolution/tune
    key uses the widest segment width (containers at mixed widths share
    one cache row per widest width)."""
    if isinstance(params, SegmentedLinearParams):
        m = x_packed.shape[0]
        k = x_packed.shape[1] * packing.pack_factor(params.a_bits)
        n = params.segmap.n
        w_key = params.segmap.widths()[0]   # widest width present
        spec, block, pipeline = _resolve_call(
            "qdot_mixed", (m, k, n), params.a_bits, w_key,
            backend=backend, block=block, pipeline=pipeline,
            plan_hints=plan_hints)
        return _run_counted(
            spec, "qdot_mixed", (m, k, n), params.a_bits, w_key, pipeline,
            lambda: spec.run(params, x_packed, epilogue=epilogue,
                             scale=scale, block=block, pipeline=pipeline),
            operands=(params, x_packed),
            w_packed_bytes=params.segmap.packed_bytes(params.k_logical))
    m = x_packed.shape[0]
    k = x_packed.shape[1] * packing.pack_factor(params.a_bits)
    n = params.w_packed.shape[1]
    spec, block, pipeline = _resolve_call(
        "qdot", (m, k, n), params.a_bits, params.w_bits, backend=backend,
        block=block, pipeline=pipeline, plan_hints=plan_hints)
    return _run_counted(
        spec, "qdot", (m, k, n), params.a_bits, params.w_bits, pipeline,
        lambda: spec.run(params, x_packed, epilogue=epilogue, scale=scale,
                         block=block, pipeline=pipeline),
        operands=(params, x_packed))


# ----------------------------------------------------------- qconv entry ---

def _conv_shape(params, x_hat):
    """qconv shape key: (n, h, w, cin, fh, fw, stride, padding, cout,
    groups). ``groups`` (grouped/depthwise conv) rides at the tail so
    ``supports()`` can reject grouped geometry it cannot lower; helpers
    accept the legacy 9-tuple (groups=1) for hand-built keys."""
    n, h, w, cin = x_hat.shape
    return (n, h, w, cin, params.fh, params.fw, params.stride,
            params.padding, params.cout, getattr(params, "groups", 1))


def conv_shape_groups(shape) -> int:
    return int(shape[9]) if len(shape) > 9 else 1


def _check_grouped(params, spec, shape):
    """Explicit ``backend=`` bypasses capability resolution, so grouped
    params must be re-checked against ``supports`` here — running a
    grouped conv through an ungrouped lowering would silently contract
    the wrong K (mis-shaped output, no error)."""
    if conv_shape_groups(shape) == 1:
        return
    if not spec.supports(shape, params.gemm.a_bits, params.gemm.w_bits,
                         platform()):
        raise ValueError(
            f"qconv backend {spec.name!r} does not support grouped conv "
            f"(groups={params.groups}); lower depthwise/grouped layers via "
            "repro.vision.layers.QDepthwiseConv2D (per-group qconv or "
            "block-diagonal im2col + qdot)")


def qconv(params, x_hat, *, epilogue: str = "int", scale=1.0,
          backend: Optional[str] = None, block: Optional[tuple] = None,
          pipeline: Optional[str] = None,
          plan_hints: Optional[dict] = None, mesh=None,
          dp_axis: str = "data", tp_axis: str = "model"):
    """Quantized HWC conv: (N, H, W, Cin) int8 images -> (N, Ho, Wo, Cout).

    params: `QuantizedConvParams` (both weight layouts built by
    `quantize_conv`, so every backend consumes bit-identical integers).
    With ``mesh=`` the call routes through `qconv_sharded`. ``pipeline``
    selects the kernel execution mode (module docstring).
    """
    if mesh is not None:
        return qconv_sharded(params, x_hat, mesh=mesh, dp_axis=dp_axis,
                             tp_axis=tp_axis, epilogue=epilogue, scale=scale,
                             backend=backend, block=block, pipeline=pipeline,
                             plan_hints=plan_hints)
    shape = _conv_shape(params, x_hat)
    g = params.gemm
    spec, block, pipeline = _resolve_call(
        "qconv", shape, g.a_bits, g.w_bits, backend=backend, block=block,
        pipeline=pipeline, plan_hints=plan_hints)
    _check_grouped(params, spec, shape)
    return _run_counted(
        spec, "qconv", shape, g.a_bits, g.w_bits, pipeline,
        lambda: spec.run(params, x_hat, epilogue=epilogue, scale=scale,
                         block=block, pipeline=pipeline),
        operands=(params, x_hat))


# ------------------------------------------------ cluster-parallel path ---

def _cluster_prologue(mesh, dp_axis, tp_axis):
    """(dp, tp, dp_spec_entry, tp_spec_entry) for a cluster call; absent
    axes act as size-1 / replicated so pure-DP and pure-TP meshes work."""
    from repro.parallel import sharding as shrules

    dp = shrules.cluster_axis_size(mesh, dp_axis)
    tp = shrules.cluster_axis_size(mesh, tp_axis)
    return dp, tp, shrules.axis_entry(mesh, dp_axis), \
        shrules.axis_entry(mesh, tp_axis)


def _reject_host_backend(spec):
    if spec.name == "eager_ref":
        raise ValueError(
            "backend 'eager_ref' is a host-side numpy oracle and cannot "
            "run under shard_map; run it on one device and compare against "
            "the sharded result instead (tests/test_cluster.py does)")
    return spec


def qdot_sharded(params, x_hat, *, mesh, dp_axis: str = "data",
                 tp_axis: str = "model", epilogue: str = "int", scale=1.0,
                 backend: Optional[str] = None,
                 block: Optional[tuple] = None,
                 pipeline: Optional[str] = None,
                 plan_hints: Optional[dict] = None):
    """`qdot` on an N-device mesh — the paper's N-core cluster (fig. 9).

    Packed weights + per-channel epilogue vectors are tensor-parallel over
    the output-feature axis N (``tp_axis``); activation rows are
    data-parallel over ``dp_axis`` (padded to a multiple, sliced back).
    K is never sharded, so each shard runs the full eq. 2-4 pipeline
    locally and the result is bit-exact vs single-device — no psum.
    The inner backend resolves on the *local* shard shape.
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel import sharding as shrules

    dp, tp, dpe, tpe = _cluster_prologue(mesh, dp_axis, tp_axis)
    wspecs = shrules.packed_linear_specs(params, mesh, tp_axis=tp_axis)

    x2, lead = _flatten_lead(x_hat)
    m = x2.shape[0]
    x2 = _pad_axis(x2, dp, 0)
    n = params.w_packed.shape[1]
    k_pad = params.w_packed.shape[0] * packing.pack_factor(params.w_bits)
    m_loc, n_loc = x2.shape[0] // dp, n // tp
    spec, block, pipeline = _resolve_call(
        "qdot", (m_loc, k_pad, n_loc), params.a_bits, params.w_bits,
        backend=backend, block=block, pipeline=pipeline,
        plan_hints=plan_hints, sharded=True)
    per_n = np.ndim(scale) == 1  # per-channel dequant scale shards with N
    sc = jnp.asarray(scale)

    def local(xs, wp, kappa, lam, mm, s):
        p_loc = dataclasses.replace(params, w_packed=wp, kappa=kappa,
                                    lam=lam, m=mm)
        xp = packing.pack(packing.pad_to_chunk(xs, axis=-1),
                          params.a_bits, axis=-1)
        return spec.run(p_loc, xp, epilogue=epilogue, scale=s, block=block,
                        pipeline=pipeline)

    # counted at the *global* GEMM size (the shard-local per-device work
    # is global/dp/tp; the dispatch event above carries the local shape)
    out = _run_counted(
        spec, "qdot", (x2.shape[0], k_pad, n), params.a_bits,
        params.w_bits, pipeline,
        lambda: jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(dpe, None), wspecs["w_packed"], wspecs["kappa"],
                      wspecs["lam"], wspecs["m"],
                      P(tpe) if per_n else P()),
            out_specs=P(dpe, tpe), check_vma=False)(
            x2, params.w_packed, params.kappa, params.lam, params.m, sc),
        operands=(params, x2))
    return out[:m].reshape(*lead, n)


def qconv_sharded(params, x_hat, *, mesh, dp_axis: str = "data",
                  tp_axis: str = "model", epilogue: str = "int", scale=1.0,
                  backend: Optional[str] = None,
                  block: Optional[tuple] = None,
                  pipeline: Optional[str] = None,
                  plan_hints: Optional[dict] = None):
    """`qconv` on an N-device mesh: images data-parallel over the batch
    dim (padded to a ``dp`` multiple, sliced back), both packed weight
    layouts + epilogue vectors tensor-parallel over Cout. Same psum-free
    bit-exactness argument as `qdot_sharded` — a device is a cluster core
    producing its own output-channel group.
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel import sharding as shrules

    dp, tp, dpe, tpe = _cluster_prologue(mesh, dp_axis, tp_axis)
    wspecs = shrules.packed_conv_specs(params, mesh, tp_axis=tp_axis)

    nb = x_hat.shape[0]
    x = _pad_axis(x_hat, dp, 0)
    g = params.gemm
    cout_loc = params.cout // tp
    shape_loc = (x.shape[0] // dp, x.shape[1], x.shape[2], x.shape[3],
                 params.fh, params.fw, params.stride, params.padding,
                 cout_loc, getattr(params, "groups", 1))
    spec, block, pipeline = _resolve_call(
        "qconv", shape_loc, g.a_bits, g.w_bits, backend=backend,
        block=block, pipeline=pipeline, plan_hints=plan_hints,
        sharded=True)
    _check_grouped(params, spec, shape_loc)
    per_n = np.ndim(scale) == 1
    sc = jnp.asarray(scale)

    def local(xs, wpf, wp, kappa, lam, mm, s):
        g_loc = dataclasses.replace(g, w_packed=wp, kappa=kappa, lam=lam,
                                    m=mm)
        p_loc = dataclasses.replace(params, gemm=g_loc, w_packed_fused=wpf,
                                    cout=cout_loc)
        return spec.run(p_loc, xs, epilogue=epilogue, scale=s, block=block,
                        pipeline=pipeline)

    shape_glob = (x.shape[0], x.shape[1], x.shape[2], x.shape[3],
                  params.fh, params.fw, params.stride, params.padding,
                  params.cout, getattr(params, "groups", 1))
    out = _run_counted(
        spec, "qconv", shape_glob, g.a_bits, g.w_bits, pipeline,
        lambda: jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(dpe, None, None, None), wspecs["w_packed_fused"],
                      wspecs["gemm"]["w_packed"], wspecs["gemm"]["kappa"],
                      wspecs["gemm"]["lam"], wspecs["gemm"]["m"],
                      P(tpe) if per_n else P()),
            out_specs=P(dpe, None, None, tpe), check_vma=False)(
            x, params.w_packed_fused, g.w_packed, g.kappa, g.lam, g.m, sc),
        operands=(params, x))
    return out[:nb]


# -------------------------------------------------------- qdot backends ---

def _require_tpu(name: str):
    plat = platform()
    if plat != "tpu":
        raise RuntimeError(
            f"backend {name!r} requires a real TPU/Mosaic platform "
            f"(got {plat!r}); select 'pallas_interpret' explicitly for "
            "interpreter-mode runs, or 'xla' for the native lowering")


def _qdot_pallas(params, x_packed, *, epilogue, scale, block,
                 pipeline: str, interpret: bool):
    """Pad M/N to the block multiples the kernel picks, run the Pallas
    packed GEMM, slice back."""
    from repro.kernels.qmatmul.kernel import default_block, qmatmul_packed

    m = x_packed.shape[0]
    k = x_packed.shape[1] * packing.pack_factor(params.a_bits)
    n = params.w_packed.shape[1]
    bm, bn, bk = block or default_block(m, n, k, params.a_bits,
                                        params.w_bits)
    bm = min(bm, round_up(m, 32))
    xp = _pad_axis(x_packed, bm, 0)
    wp = _pad_axis(params.w_packed, bn, 1)
    kappa = _pad_axis(params.kappa, bn, 0)
    lam = _pad_axis(params.lam, bn, 0)
    mm = _pad_axis(params.m, bn, 0)
    out = qmatmul_packed(
        xp, wp, kappa, lam, mm, a_bits=params.a_bits,
        a_signed=params.a_signed, w_bits=params.w_bits, d=params.d,
        out_bits=params.out_bits, epilogue=epilogue, scale=scale,
        block=(bm, bn, bk), pipeline=pipeline, interpret=interpret)
    return out[:m, :n]


def _qdot_pallas_run(params, x_packed, *, epilogue, scale, block=None,
                     pipeline: str = "off"):
    _require_tpu("pallas")
    return _qdot_pallas(params, x_packed, epilogue=epilogue, scale=scale,
                        block=block, pipeline=pipeline, interpret=False)


def _qdot_interpret_run(params, x_packed, *, epilogue, scale, block=None,
                        pipeline: str = "off"):
    return _qdot_pallas(params, x_packed, epilogue=epilogue, scale=scale,
                        block=block, pipeline=pipeline, interpret=True)


def _qdot_xla_run(params, x_packed, *, epilogue, scale, block=None,
                  pipeline: str = "off"):
    del block, pipeline  # XLA picks its own tiling/pipelining
    x = packing.unpack(x_packed, params.a_bits, params.a_signed, axis=-1)
    return xla_int_gemm(
        x, params.w_packed, w_bits=params.w_bits, kappa=params.kappa,
        lam=params.lam, m_mul=params.m, d=params.d,
        out_bits=params.out_bits, epilogue=epilogue, scale=scale)


def _qdot_eager_run(params, x_packed, *, epilogue, scale, block=None,
                    pipeline: str = "off"):
    del block, pipeline
    from repro.kernels.qmatmul.ref import qmatmul_ref

    if np.ndim(scale) > 0:
        raise NotImplementedError("eager_ref qdot: scalar scale only")
    out = qmatmul_ref(
        np.asarray(x_packed), np.asarray(params.w_packed),
        np.asarray(params.kappa), np.asarray(params.lam),
        np.asarray(params.m), a_bits=params.a_bits,
        a_signed=params.a_signed, w_bits=params.w_bits, d=params.d,
        out_bits=params.out_bits, epilogue=epilogue, scale=float(scale))
    dtype = {"int": jnp.int8, "dequant": jnp.bfloat16,
             "raw": jnp.int32}[epilogue]
    return jnp.asarray(out).astype(dtype)


# -------------------------------------------------- qdot_mixed backends ---

def _qdot_mixed_pallas(params, x_packed, *, epilogue, scale, block,
                       pipeline: str, interpret: bool):
    """Mixed-operand Pallas path: zero-pad the ragged tail panel of the
    segmented container to a full CHUNK (`pad_segmented` — the artifact
    itself stays exact-bytes), pad M to the block multiple, run
    `qmatmul_segmented`, slice back."""
    from repro.kernels.common import LANE, segmented_default_block
    from repro.kernels.qmatmul.kernel import qmatmul_segmented

    if np.ndim(scale) > 0:
        raise NotImplementedError(
            "pallas qdot_mixed: scalar scale only (like the uniform "
            "kernel); use backend='xla' for per-channel dequant scales")
    m = x_packed.shape[0]
    k_pad = x_packed.shape[1] * packing.pack_factor(params.a_bits)
    n = params.segmap.n
    w_flat, segmap_p = packing.pad_segmented(
        params.w_flat, params.segmap, params.k_logical)
    if block is None:
        bm, bk = segmented_default_block(m, k_pad, params.a_bits,
                                         params.segmap.widths())
    else:
        bm, bk = block[0], block[2]
    bm = min(bm, round_up(m, 32))
    xp = _pad_axis(x_packed, bm, 0)
    kappa = _pad_axis(params.kappa, LANE, 0)
    lam = _pad_axis(params.lam, LANE, 0)
    mm = _pad_axis(params.m, LANE, 0)
    out = qmatmul_segmented(
        xp, w_flat, segmap_p, kappa, lam, mm, k_logical=params.k_logical,
        a_bits=params.a_bits, a_signed=params.a_signed, d=params.d,
        out_bits=params.out_bits, epilogue=epilogue, scale=scale,
        block=(bm, LANE, bk), pipeline=pipeline, interpret=interpret)
    return out[:m, :n]


def _qdot_mixed_pallas_run(params, x_packed, *, epilogue, scale, block=None,
                           pipeline: str = "off"):
    _require_tpu("pallas")
    return _qdot_mixed_pallas(params, x_packed, epilogue=epilogue,
                              scale=scale, block=block, pipeline=pipeline,
                              interpret=False)


def _qdot_mixed_interpret_run(params, x_packed, *, epilogue, scale,
                              block=None, pipeline: str = "off"):
    return _qdot_mixed_pallas(params, x_packed, epilogue=epilogue,
                              scale=scale, block=block, pipeline=pipeline,
                              interpret=True)


def _qdot_mixed_xla_run(params, x_packed, *, epilogue, scale, block=None,
                        pipeline: str = "off"):
    """Segment-looping XLA fallback: each run is a uniform container view
    (`segment_packed`), so each goes through `xla_int_gemm` with its own
    width and epilogue slice; outputs concatenate along N."""
    del block, pipeline
    x = packing.unpack(x_packed, params.a_bits, params.a_signed, axis=-1)
    outs = []
    for i, (s, e, b) in enumerate(params.segmap.runs):
        sp = params.segment_params(i)
        sc = scale if np.ndim(scale) == 0 else scale[..., s:e]
        outs.append(xla_int_gemm(
            x, sp.w_packed, w_bits=b, kappa=sp.kappa, lam=sp.lam,
            m_mul=sp.m, d=sp.d, out_bits=sp.out_bits, epilogue=epilogue,
            scale=sc))
    return jnp.concatenate(outs, axis=-1)


def _qdot_mixed_eager_run(params, x_packed, *, epilogue, scale, block=None,
                          pipeline: str = "off"):
    del block, pipeline
    from repro.kernels.qmatmul.ref import qmatmul_ref

    if np.ndim(scale) > 0:
        raise NotImplementedError("eager_ref qdot_mixed: scalar scale only")
    outs = []
    for i in range(len(params.segmap.runs)):
        sp = params.segment_params(i)
        outs.append(qmatmul_ref(
            np.asarray(x_packed), np.asarray(sp.w_packed),
            np.asarray(sp.kappa), np.asarray(sp.lam), np.asarray(sp.m),
            a_bits=sp.a_bits, a_signed=sp.a_signed, w_bits=sp.w_bits,
            d=sp.d, out_bits=sp.out_bits, epilogue=epilogue,
            scale=float(scale)))
    dtype = {"int": jnp.int8, "dequant": jnp.bfloat16,
             "raw": jnp.int32}[epilogue]
    return jnp.asarray(np.concatenate(outs, axis=-1)).astype(dtype)


# ------------------------------------------------------- qconv backends ---

def _conv_fits_vmem(shape, a_bits, w_bits) -> bool:
    from repro.kernels.common import conv_default_block

    if conv_shape_groups(shape) != 1:
        return False  # the fused kernel contracts the full fh*fw*cin axis
    n, h, w, cin, fh, fw, stride, padding, cout = shape[:9]
    ho = (h + 2 * padding - fh) // stride + 1
    wo = (w + 2 * padding - fw) // stride + 1
    if ho <= 0 or wo <= 0:
        return False
    try:
        conv_default_block(n, ho, wo, cout, fh, fw,
                           packing.padded_size(cin), stride, a_bits, w_bits)
        return True
    except ValueError:
        return False


def _qconv_fused(params, x_hat, *, epilogue, scale, block, pipeline: str,
                 interpret: bool):
    from repro.kernels.qconv.kernel import qconv2d_fused

    g = params.gemm
    return qconv2d_fused(
        x_hat, params.w_packed_fused, g.kappa, g.lam, g.m,
        fh=params.fh, fw=params.fw, stride=params.stride,
        padding=params.padding, cin_pad=params.cin_pad, cout=params.cout,
        a_bits=g.a_bits, a_signed=g.a_signed, w_bits=g.w_bits, d=g.d,
        out_bits=g.out_bits, epilogue=epilogue, scale=scale, block=block,
        pipeline=pipeline, interpret=interpret)


def _qconv_pallas_run(params, x_hat, *, epilogue, scale, block=None,
                      pipeline: str = "off"):
    _require_tpu("pallas")
    return _qconv_fused(params, x_hat, epilogue=epilogue, scale=scale,
                        block=block, pipeline=pipeline, interpret=False)


def _qconv_interpret_run(params, x_hat, *, epilogue, scale, block=None,
                         pipeline: str = "off"):
    return _qconv_fused(params, x_hat, epilogue=epilogue, scale=scale,
                        block=block, pipeline=pipeline, interpret=True)


def _qconv_xla_run(params, x_hat, *, epilogue, scale, block=None,
                   pipeline: str = "off"):
    del block, pipeline
    from repro.kernels.qconv.ops import im2col_hwc  # lazy: ops imports api

    cols, ho, wo = im2col_hwc(x_hat, params.fh, params.fw, params.stride,
                              params.padding)
    y = qdot(params.gemm, cols, epilogue=epilogue, scale=scale,
             backend="xla")
    return y.reshape(x_hat.shape[0], ho, wo, params.cout)


def _qconv_eager_run(params, x_hat, *, epilogue, scale, block=None,
                     pipeline: str = "off"):
    del block, pipeline
    from repro.kernels.qconv.ref import qconv2d_ref
    from repro.kernels.qmatmul.ref import unpack_np

    if epilogue != "int":
        raise NotImplementedError("eager_ref qconv: 'int' epilogue only")
    g = params.gemm
    w_flat = unpack_np(np.asarray(params.w_packed_fused), g.w_bits, True,
                       axis=0)
    w_tap = w_flat.reshape(params.fh * params.fw, params.cin_pad,
                           params.cout)[:, :params.cin, :]
    w_hat = w_tap.reshape(params.fh, params.fw, params.cin, params.cout)
    out = qconv2d_ref(np.asarray(x_hat), w_hat, np.asarray(g.kappa),
                      np.asarray(g.lam), np.asarray(g.m), g.d, g.out_bits,
                      stride=params.stride, padding=params.padding)
    return jnp.asarray(out)


# --------------------------------------------------------- registrations ---

def _on_tpu(shape, a_bits, w_bits, plat) -> bool:
    return plat == "tpu"


def _always(shape, a_bits, w_bits, plat) -> bool:
    return True


def _conv_ungrouped(shape, a_bits, w_bits, plat) -> bool:
    # every registered conv lowering contracts one full fh*fw*cin GEMM;
    # grouped/depthwise geometry must be lowered above the registry
    # (repro.vision.layers) until a grouped backend registers itself
    return conv_shape_groups(shape) == 1


register("qdot", "pallas", supports=_on_tpu, run=_qdot_pallas_run,
         doc="Mosaic packed sub-byte GEMM kernel (TPU only)")
register("qdot", "pallas_interpret", supports=_always,
         run=_qdot_interpret_run,
         doc="same kernel under the Pallas interpreter (tests/dry-runs)")
register("qdot", "xla", supports=_always, run=_qdot_xla_run,
         doc="XLA-native unpack + int dot_general + fused epilogue")
register("qdot", "eager_ref", supports=_always, run=_qdot_eager_run,
         doc="independent numpy oracle (bit-exactness baseline)")

register("qdot_mixed", "pallas", supports=_on_tpu,
         run=_qdot_mixed_pallas_run,
         doc="mixed-operand segmented GEMM kernel (per-tile unpack width)")
register("qdot_mixed", "pallas_interpret", supports=_always,
         run=_qdot_mixed_interpret_run,
         doc="mixed-operand kernel under the Pallas interpreter")
register("qdot_mixed", "xla", supports=_always, run=_qdot_mixed_xla_run,
         doc="segment-looping XLA fallback (uniform int GEMM per run)")
register("qdot_mixed", "eager_ref", supports=_always,
         run=_qdot_mixed_eager_run,
         doc="segment-looping numpy oracle (uniform ref GEMM per run)")

register("qconv", "pallas",
         supports=lambda s, a, w, p: p == "tpu" and _conv_fits_vmem(s, a, w),
         run=_qconv_pallas_run,
         doc="fused implicit-GEMM conv kernel (TPU only, VMEM-bounded)")
register("qconv", "pallas_interpret",
         supports=lambda s, a, w, p: _conv_fits_vmem(s, a, w),
         run=_qconv_interpret_run,
         doc="fused conv kernel under the Pallas interpreter")
register("qconv", "xla", supports=_conv_ungrouped, run=_qconv_xla_run,
         doc="XLA im2col + xla qdot (also the large-image fallback)")
register("qconv", "eager_ref", supports=_conv_ungrouped,
         run=_qconv_eager_run,
         doc="direct-convolution numpy oracle (no shared im2col path)")
