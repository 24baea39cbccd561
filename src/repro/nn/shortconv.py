"""Gated short convolution (LFM2's conv operator).

    B, C, x = split3(in_proj(x))           in_proj: d -> 3d, no bias
    y = out_proj(C * causal_dwconv(B * x))   out_proj: d -> d, no bias

The depthwise causal conv has a float kernel ``conv`` of shape (K, d), K
= ``kernel`` (LFM2's ``conv_L_cache``, 3), no bias:
``out[t] = sum_j conv[j] * u[t - (K - 1) + j]``, zeros before the first
position (PyTorch's ``Conv1d(groups=d, padding=K-1)`` cut to the
sequence). Both projections are quantization-aware Dense layers, packed
under ``QuantConfig(mode="int")``.

Decode carries a rolling state of the last K - 1 values of ``B * x`` per
channel, (batch, K - 1, d), oldest first: a re-admitted serving slot
must start from zeros (`repro.serve.runtime.adapters.STATE_RESET_KEYS`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.deploy.policy import PrecisionPlan, resolve_qcfg
from repro.nn.layers import QOFF, QuantConfig, dense_apply, dense_def
from repro.nn.module import ParamDef


@dataclasses.dataclass(frozen=True)
class ShortConvConfig:
    d_model: int
    kernel: int = 3
    qcfg: QuantConfig = QOFF
    plan: Optional[PrecisionPlan] = None
    path: str = "conv_layers/conv"

    def q(self, name: str) -> QuantConfig:
        return resolve_qcfg(self.plan, f"{self.path}/{name}", self.qcfg)


def shortconv_def(cfg: ShortConvConfig, dtype=jnp.float32):
    d = cfg.d_model
    return {"in_proj": dense_def(d, 3 * d, ("embed", "mlp"),
                                 qcfg=cfg.q("in_proj"), dtype=dtype),
            "conv": ParamDef((cfg.kernel, d), (None, "embed"), "normal",
                             dtype),
            "out_proj": dense_def(d, d, ("mlp", "embed"),
                                  qcfg=cfg.q("out_proj"), dtype=dtype)}


def _gates(p, x, cfg: ShortConvConfig):
    bcx = dense_apply(p["in_proj"], x, qcfg=cfg.q("in_proj"))
    b, c, xx = jnp.split(bcx, 3, axis=-1)
    return b * xx, c


def shortconv_apply(p, x, cfg: ShortConvConfig):
    """Whole sequence. x: (B, S, d) -> (B, S, d)."""
    u, c = _gates(p, x, cfg)
    k, s = cfg.kernel, x.shape[1]
    w = p["conv"].astype(jnp.float32)
    up = jnp.pad(u.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(w[j] * up[:, j:j + s] for j in range(k))
    return dense_apply(p["out_proj"], (c * conv.astype(c.dtype)),
                       qcfg=cfg.q("out_proj"))


def shortconv_init_state(cfg: ShortConvConfig, batch: int,
                         dtype=jnp.bfloat16):
    return jnp.zeros((batch, cfg.kernel - 1, cfg.d_model), dtype)


def shortconv_decode(p, x, state, cfg: ShortConvConfig):
    """One token. x: (B, 1, d); state: (B, K-1, d) -> (y, new state)."""
    u, c = _gates(p, x, cfg)
    window = jnp.concatenate([state, u.astype(state.dtype)], axis=1)
    conv = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32),
                      p["conv"].astype(jnp.float32))[:, None]
    y = dense_apply(p["out_proj"], (c * conv.astype(c.dtype)),
                    qcfg=cfg.q("out_proj"))
    return y, window[:, 1:]
