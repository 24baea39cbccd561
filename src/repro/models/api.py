"""Model registry: one uniform API over all families.

Model exposes: init / specs / loss / forward / prefill / decode / init_cache
/ input_specs. The dry-run, trainer, server, and benchmarks only talk to
this API.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, griffin, lm, mamba
from repro.nn.module import init_params, logical_specs

_FAMILIES = {
    "lm": (lm.lm_def, lm.forward, lm.decode_step, lm.lm_init_cache),
    "encdec": (encdec.encdec_def, encdec.forward, encdec.decode_step,
               encdec.encdec_init_cache),
    "mamba": (mamba.mamba_lm_def, mamba.forward, mamba.decode_step,
              mamba.mamba_lm_init_cache),
    "griffin": (griffin.griffin_def, griffin.forward, griffin.decode_step,
                griffin.griffin_init_cache),
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    @property
    def _fns(self):
        return _FAMILIES[self.cfg.family]

    # ---- params ----
    def defs(self, dtype=jnp.float32):
        pd = jnp.float32 if self.cfg.param_dtype == "float32" else jnp.bfloat16
        return self._fns[0](self.cfg, pd)

    def init(self, key):
        return init_params(self.defs(), key)

    def specs(self):
        return logical_specs(self.defs())

    # ---- training ----
    def loss(self, params, batch, aux_weight: float = 0.01):
        logits, aux, _ = self._fns[1](
            params, batch["tokens"], self.cfg,
            src_embed=batch.get("src_embed"))
        logits = logits.astype(jnp.float32)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        # z-loss keeps logits bounded (stability at scale)
        zl = 1e-4 * jnp.square(jax.nn.logsumexp(logits, axis=-1))
        return jnp.mean(nll) + jnp.mean(zl) + aux_weight * aux

    def forward(self, params, batch):
        return self._fns[1](params, batch["tokens"], self.cfg,
                            src_embed=batch.get("src_embed"))

    # ---- serving ----
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return self._fns[3](self.cfg, batch, max_len, dtype)

    def prefill(self, params, batch):
        """Full forward over the prompt; returns last-position logits.
        (Cache population from prefill KV is handled in serve/engine.py.)"""
        logits, _, kvs = self._fns[1](
            params, batch["tokens"], self.cfg,
            src_embed=batch.get("src_embed"), collect_kv=True)
        return logits[:, -1:], kvs

    def decode(self, params, cache, token, index, src_embed=None):
        """(logits, cache), and for a hybrid MoE LM the experts each MoE
        layer hit (``lm.decode_step``)."""
        return self._fns[2](params, cache, token, index, self.cfg,
                            src_embed=src_embed)

    @property
    def experts_hit_layers(self) -> int:
        """Length of the experts hit a decode step returns (0: none)."""
        return lm.experts_hit_layers(self.cfg)

    # ---- shapes for dry-run / launchers ----
    def input_specs(self, shape: ShapeConfig):
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32, bf16 = jnp.int32, jnp.bfloat16
        d = cfg.d_model
        if shape.kind == "train":
            spec = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                    "labels": jax.ShapeDtypeStruct((b, s), i32)}
            if _needs_src(cfg):
                spec["src_embed"] = jax.ShapeDtypeStruct((b, s, d), bf16)
            return spec
        if shape.kind == "prefill":
            if cfg.family == "encdec":
                # long input lives on the encoder side; short decoder draft
                return {"tokens": jax.ShapeDtypeStruct((b, 256), i32),
                        "src_embed": jax.ShapeDtypeStruct((b, s, d), bf16)}
            spec = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
            if _needs_src(cfg):
                spec["src_embed"] = jax.ShapeDtypeStruct(
                    (b, cfg.src_len, d), bf16)
            return spec
        # decode: one new token against a seq_len-deep cache
        cache = jax.eval_shape(functools.partial(self.init_cache, b, s))
        spec = {"token": jax.ShapeDtypeStruct((b, 1), i32),
                "index": jax.ShapeDtypeStruct((), i32),
                "cache": cache}
        return spec


def _needs_src(cfg: ModelConfig) -> bool:
    return cfg.family == "encdec" or cfg.cross_every > 0


_REGISTRY: dict = {}
_LOADED = False  # `not _REGISTRY` is the wrong guard: importing any single
# config module registers it and would mask the rest forever


def register(cfg: ModelConfig):
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if not _LOADED:
        _load_all()
    return _REGISTRY[name]


def list_archs():
    if not _LOADED:
        _load_all()
    return sorted(_REGISTRY)


def _load_all():
    global _LOADED
    import importlib
    import pkgutil

    import repro.configs as cpkg
    for mod in pkgutil.iter_modules(cpkg.__path__):
        if mod.name not in ("base",):
            importlib.import_module(f"repro.configs.{mod.name}")
    _LOADED = True


def get_smoke_config(name: str) -> ModelConfig:
    """Reduced same-family config for the arch with this registry name."""
    import importlib
    import pkgutil

    import repro.configs as cpkg
    for mod in pkgutil.iter_modules(cpkg.__path__):
        if mod.name == "base":
            continue
        m = importlib.import_module(f"repro.configs.{mod.name}")
        if getattr(m, "CONFIG", None) is not None and m.CONFIG.name == name:
            return m.smoke_config()
    raise KeyError(name)


def build(name_or_cfg) -> Model:
    cfg = (name_or_cfg if isinstance(name_or_cfg, ModelConfig)
           else get_config(name_or_cfg))
    return Model(cfg)
