"""GPipe-style pipeline parallelism over the `pod` mesh axis.

For the 100B+ archs the pod axis can carry pipeline stages instead of DP:
layer stacks are split into n_stages contiguous stages (stage s holds the
(s * L/n,. ..) slice of the stacked params, sharded on the stacking dim
over `pod`), and microbatches flow through a shard_map ring: every step,
each stage applies its layers to the activation it holds and
collective-permutes the result to the next stage. Bubble fraction =
(n_stages - 1) / (n_micro + n_stages - 1).

Inter-pod links are the slowest in the hierarchy, which is exactly why
pipelining (O(activations) point-to-point per microbatch) beats DP
(O(grads) all-reduce) across pods at the 1T scale — see DESIGN.md §5.

**Paper analogy:** the pod axis is the *multi-cluster* tier — the paper's
SoC instantiating several 8-core clusters — while the in-pod `model` axis
is the cluster itself (`repro.parallel.sharding`, device ↔ core). Stage
params may be packed sub-byte artifacts: the stacking dim (dim0 of each
stage slice) is a layer index, not a tensor axis, so sharding it over
`pod` never touches the packed reduction axis and the per-stage kernels
keep the psum-free epilogue invariant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_micro, mesh: Mesh,
                   axis: str = "pod"):
    """Run a GPipe forward.

    stage_fn(params_slice, h) -> h : applies ONE stage's layers.
    stage_params: pytree with leaves stacked (n_stages, ...) — sharded on
      dim0 over `axis` (each pod holds its stage's layers).
    x_micro: (n_micro, mb, ...) microbatched input, replicated.
    Returns (n_micro, mb, ...) outputs, replicated (psum-broadcast from
    the last stage).
    """
    n_stages = mesh.shape[axis]
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def local(sp, xm):
        s = jax.lax.axis_index(axis)
        sp = jax.tree.map(lambda a: a[0], sp)  # (1, ...) shard -> stage tree
        n_micro = xm.shape[0]
        total = n_micro + n_stages - 1
        out = jnp.zeros_like(xm)
        cur = jnp.zeros_like(xm[0])

        def step(t, carry):
            out, cur = carry
            # stage 0 ingests microbatch t while it exists
            inj = xm[jnp.minimum(t, n_micro - 1)]
            h_in = jnp.where(s == 0, inj, cur)
            h_out = stage_fn(sp, h_in)
            # emit: the last stage finishes microbatch t - (n_stages - 1)
            idx = t - (n_stages - 1)
            take = jnp.logical_and(s == n_stages - 1,
                                   jnp.logical_and(idx >= 0, idx < n_micro))
            slot = jnp.clip(idx, 0, n_micro - 1)
            out = jnp.where(
                take, out.at[slot].set(h_out), out)
            cur = jax.lax.ppermute(h_out, axis, perm)
            return out, cur

        out, _ = jax.lax.fori_loop(0, total, step, (out, cur))
        # broadcast the last stage's outputs to every stage
        mask = (s == n_stages - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params,
                               is_leaf=lambda x: hasattr(x, "shape")),
                  P()),
        out_specs=P(), check_vma=False)(stage_params, x_micro)


def stage_stack(params_stacked, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""
    return jax.tree.map(
        lambda a: a.reshape(n_stages, a.shape[0] // n_stages, *a.shape[1:]),
        params_stacked)
