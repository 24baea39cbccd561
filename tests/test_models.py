"""Per-arch smoke tests: reduced configs, one forward + loss + grad, shape
and finiteness checks (deliverable f)."""
import importlib
import pkgutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cpkg
from repro.models.api import build, list_archs

MODS = sorted(m.name for m in pkgutil.iter_modules(cpkg.__path__)
              if m.name != "base")
# big/exotic archs are several seconds each even at smoke size; keep a
# representative fast set per family, run the rest with --runslow
_HEAVY = {"recurrentgemma_9b", "llama3p2_vision_90b", "llama4_maverick_400b",
          "kimi_k2_1t", "seamless_m4t_large_v2", "gemma3_1b", "qwen2p5_3b"}


def _arch_params(names):
    return [pytest.param(n, marks=pytest.mark.slow) if n in _HEAVY else n
            for n in names]


@pytest.mark.parametrize("modname", _arch_params(MODS))
def test_smoke_forward(modname):
    m = importlib.import_module(f"repro.configs.{modname}")
    cfg = m.smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    if cfg.family == "encdec" or cfg.cross_every:
        sl = S if cfg.family == "encdec" else cfg.src_len
        batch["src_embed"] = jnp.ones((B, sl, cfg.d_model),
                                      jnp.bfloat16) * 0.01
    from repro.nn.layers import padded_vocab
    logits, aux, _ = model.forward(params, batch)
    assert logits.shape == (B, S, padded_vocab(cfg.vocab))
    # padded vocab rows masked to -1e9; real rows finite
    real = np.asarray(logits, np.float32)[..., :cfg.vocab]
    assert np.isfinite(real).all()
    loss = model.loss(params, batch)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize(
    "modname",
    _arch_params(["qwen2p5_3b", "mamba2_370m", "recurrentgemma_9b"]))
def test_grad_finite(modname):
    m = importlib.import_module(f"repro.configs.{modname}")
    cfg = m.smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    g = jax.grad(lambda p: model.loss(p, batch))(params)
    gn = sum(float(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


def test_all_archs_registered():
    assert len(list_archs()) == 11


def test_full_configs_match_assignment():
    """The exact published numbers from the assignment table."""
    from repro.models.api import get_config
    c = get_config("gemma3-1b")
    assert (c.n_layers, c.d_model, c.n_heads, c.kv_heads, c.d_ff,
            c.vocab) == (26, 1152, 4, 1, 6912, 262144)
    c = get_config("kimi-k2-1t-a32b")
    assert (c.n_layers, c.d_model, c.n_heads, c.kv_heads, c.vocab) == \
        (61, 7168, 64, 8, 163840)
    assert (c.moe.n_experts, c.moe.top_k, c.moe.d_ff) == (384, 8, 2048)
    c = get_config("llama-3.2-vision-90b")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab) == \
        (100, 8192, 28672, 128256)
    c = get_config("mamba2-370m")
    assert (c.n_layers, c.d_model, c.d_state, c.vocab) == \
        (48, 1024, 128, 50280)
    c = get_config("seamless-m4t-large-v2")
    assert (c.d_model, c.d_ff, c.vocab) == (1024, 8192, 256206)
