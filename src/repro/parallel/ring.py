"""Explicit shard_map collectives: ring decode-attention and collective
matmul — the "below GSPMD" tools the §Perf Cell-B analysis identified
(GSPMD cannot repartition gathers/5-D einsum backwards across changed
layouts and falls back to replication; writing the collective schedule by
hand fixes the pattern).

**Paper analogy:** each shard_map body here is what one core of the
XpulpNN cluster executes between synchronization points — the ring
permutes play the role of the cluster's TCDM interconnect moving operand
tiles between cores. Contrast with the *psum-free* quantized cluster path
(`repro.kernels.api.qdot_sharded`): integer QNN GEMMs shard the
output-feature axis and need no collective at all, while the float
attention/matmul patterns here genuinely need cross-device combines —
which is why they get hand-written schedules. Packed sub-byte operands
never enter these ring paths: the sharding invariant (packed reduction
axis unsharded, `repro.parallel.sharding`) means a K-sharded collective
matmul over packed weights would split CHUNK containers and is rejected
at spec level.

ring_decode_attention — flash-decoding over a KV cache sequence-sharded on
the `model` axis: each shard computes partial (numerator, denominator,
max) over its KV slice and one log-sum-exp combine (psum of O(B*H*Dh))
merges them — instead of all-gathering O(B*H*T) scores. This is the
long_500k serving path for the global layers.

collective_matmul — all-gather-overlapped GEMM (Wang et al.): x arrives
K-sharded, w is N-sharded; each ring hop multiplies the resident x shard
against the matching K-block of the local w columns while the next x
shard is collective-permuted in. The MXU hides the transfer; no
materialized all-gather buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ring_decode_attention(q, k_shard, v_shard, valid_mask, mesh: Mesh,
                          axis: str = "model"):
    """q: (B,H,Dh) replicated over `axis`; k/v: (B,T,H,Dh) KV-sequence
    sharded on T over `axis`; valid_mask: (B,T) bool. Returns (B,H,Dh)."""

    def local(q, k, v, mask):
        dh = q.shape[-1]
        s = jnp.einsum("bhd,bthd->bht", q, k,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        s = jnp.where(mask[:, None, :], s, -jnp.inf)
        m_loc = jnp.max(s, axis=-1)                        # (B,H)
        has = jnp.isfinite(m_loc)
        safe_m = jnp.where(has, m_loc, 0.0)
        p = jnp.where(mask[:, None, :],
                      jnp.exp(s - safe_m[..., None]), 0.0)
        num = jnp.einsum("bht,bthd->bhd", p.astype(v.dtype), v)
        den = jnp.sum(p, axis=-1)                          # (B,H)
        m_glob = jax.lax.pmax(jnp.where(has, m_loc, -jnp.inf), axis)
        scale = jnp.exp(safe_m - m_glob) * has
        num = jax.lax.psum(num * scale[..., None].astype(num.dtype), axis)
        den = jax.lax.psum(den * scale, axis)
        return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)

    spec_kv = P(None, axis, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), spec_kv, spec_kv, P(None, axis)),
        out_specs=P(), check_vma=False)(q, k_shard, v_shard, valid_mask)


def collective_matmul(x, w, mesh: Mesh, axis: str = "model"):
    """y = x @ w. x: (M,K) sharded on K over `axis`; w: (K,N) sharded on N
    over `axis`. Returns y (M,N) sharded on N.

    Ring schedule: after i hops device d holds x shard (d - i) mod n and
    multiplies it with its own w rows [(d-i)*kloc : (d-i+1)*kloc, :] —
    every (x_shard_j, w_block_j) pair is formed exactly once.
    """
    n = mesh.shape[axis]
    perm = [(j, (j + 1) % n) for j in range(n)]

    def local(x_loc, w_loc):
        idx = jax.lax.axis_index(axis)
        kloc = x_loc.shape[-1]
        acc = jnp.zeros((x_loc.shape[0], w_loc.shape[1]),
                        jnp.promote_types(x_loc.dtype, w_loc.dtype))

        def body(i, carry):
            acc, xs = carry
            src = (idx - i) % n
            block = jax.lax.dynamic_slice_in_dim(w_loc, src * kloc, kloc, 0)
            acc = acc + xs @ block
            xs = jax.lax.ppermute(xs, axis, perm)
            return acc, xs

        acc, _ = jax.lax.fori_loop(0, n, body, (acc, x_loc))
        return acc.astype(x_loc.dtype)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(None, axis), P(None, axis)),
                         out_specs=P(None, axis), check_vma=False)(x, w)
