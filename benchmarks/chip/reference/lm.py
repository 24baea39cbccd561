"""Plain float32 reference of a Qwen2-style decoder served at W{w}A{a}.

Follows the published architecture (pre-norm RMSNorm blocks, GQA
attention with q/k/v bias and rotate-half RoPE, SwiGLU MLP, tied or
untied head) and the configuration's serving arithmetic, written out
here in ``jax.numpy`` without anything of the program:

  * weights: the chunk-planar packed codes are unpacked by this file's
    own decoder and scaled per output channel by ``w_scale``;
  * activations: before every projection, quantized onto the symmetric
    ``a_bits`` grid of the static range ``a_absmax`` (the configuration's
    static A8 scale), the integer product is exact in float32, then
    scaled back;
  * everything else (norms, RoPE, softmax, residual stream, KV, head) is
    float32 at ``default_matmul_precision("highest")``: the reference
    keeps no bf16 rounding of the residual stream or of the KV cache.

The whole sequence runs at once (prompt plus served tokens), one layer
at a time under ``lax.scan``, so only one layer's weights are unpacked
at a time. ``variant`` lowers one precision for the control:
``{"a_bits": 4}`` or ``{"kv_bits": 8}`` (K/V on the static int8 grid).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 128
_A_MAX = {8: 127, 4: 7, 2: 1}       # symmetric activation grid
_KV8_SCALE = 8.0 / 127.0


def unpack(packed, bits: int):
    """(..., K/pf, N) int8 containers -> (..., K, N) float32 codes.

    Within each chunk of 128 rows, packed row j holds rows j + p*128/pf
    in bit field p (low to high), as signed two's complement."""
    if bits == 8:
        return packed.astype(jnp.float32)
    pf = 8 // bits
    sub = CHUNK // pf
    *lead, kp, n = packed.shape
    u = packed.astype(jnp.int32) & 0xFF
    c = u.reshape(*lead, kp // sub, sub, n)
    half, full = 1 << (bits - 1), 1 << bits
    fields = []
    for p in range(pf):
        f = (c >> (bits * p)) & (full - 1)
        fields.append(jnp.where(f >= half, f - full, f))
    out = jnp.stack(fields, axis=-3)            # (..., chunks, pf, sub, N)
    return out.reshape(*lead, kp * pf, n).astype(jnp.float32)


def _rms(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def _dense(p, x, w_bits, a_bits, a_absmax):
    s = a_absmax / _A_MAX[a_bits]
    xq = jnp.clip(jnp.round(x / s), -_A_MAX[a_bits], _A_MAX[a_bits])
    w = unpack(p["w_packed"], w_bits)[: x.shape[-1]]
    y = jnp.matmul(xq, w) * (p["w_scale"] * s)
    if "b" in p:
        y = y + p["b"]
    return y


def _rope(x, theta):
    """x (B, T, H, Dh): rotate-half RoPE at positions 0..T-1."""
    t = x.shape[1]
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    c = jnp.cos(ang)[None, :, None, :]
    s = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _kv_round(x, kv_bits):
    if kv_bits == 8:
        return jnp.clip(jnp.round(x / _KV8_SCALE), -127, 127) * _KV8_SCALE
    return x


def hidden_states(params, tokens, cfg: dict, variant: dict):
    """Final-norm hidden states (B, T, d) of ``tokens`` (B, T)."""
    srv = cfg["serving"]
    w_bits = srv["w_bits"]
    a_bits = variant.get("a_bits", srv["a_bits"])
    kv_bits = variant.get("kv_bits", 16)
    absmax = srv["a_absmax"]
    eps = cfg["rms_norm_eps"]
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hk = cfg["num_key_value_heads"]
    dh = d // h
    g = h // hk
    theta = float(cfg["rope_theta"])
    dense = functools.partial(_dense, w_bits=w_bits, a_bits=a_bits,
                              a_absmax=absmax)
    b, t = tokens.shape
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def layer(x, lp):
        a = lp["attn"]
        hn = _rms(x, lp["ln1"]["scale"], eps)
        q = dense(a["wq"], hn).reshape(b, t, hk, g, dh)
        k = dense(a["wk"], hn).reshape(b, t, hk, dh)
        v = dense(a["wv"], hn).reshape(b, t, hk, dh)
        q = _rope(q.reshape(b, t, h, dh), theta).reshape(b, t, hk, g, dh)
        k = _kv_round(_rope(k, theta), kv_bits)
        v = _kv_round(v, kv_bits)
        sc = jnp.einsum("bthgd,bshd->bhgts", q, k) * (dh ** -0.5)
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhgts,bshd->bthgd", pr, v).reshape(b, t, h * dh)
        x = x + dense(a["wo"], o)
        m = lp["mlp"]
        hn = _rms(x, lp["ln2"]["scale"], eps)
        act = jax.nn.silu(dense(m["wg"], hn)) * dense(m["wi"], hn)
        return x + dense(m["wo"], act), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, params["layers"])
        return _rms(x, params["final_norm"]["scale"], eps)


def _head(params, hs, cfg):
    """Logits (B, C, V) of hidden states (B, C, d), padded rows cut."""
    with jax.default_matmul_precision("highest"):
        if cfg.get("tie_word_embeddings", False):
            lg = jnp.einsum("bcd,vd->bcv", hs, params["embed"]["table"])
        else:
            raise NotImplementedError("untied head")
    return lg[..., : cfg["vocab_size"]]


def gaps(params, tokens, cfg: dict, chunk: int = 128, control=None):
    """Per position p of ``tokens`` (B, T), the gap by which a token's
    reference logit lies below the reference's best logit at p.

    Without ``control``: the token is the one that follows, tokens[:,
    p+1] (the served token). With ``control`` (a variant): the token the
    control's logits put first at p. Returns (B, T-1) float32."""
    hs = hidden_states(params, tokens, cfg, {})
    hc = None if control is None else hidden_states(params, tokens, cfg,
                                                    control)
    b, t = tokens.shape
    nxt = tokens[:, 1:]
    nch = (t - 1 + chunk - 1) // chunk
    pad = nch * chunk - (t - 1)
    hs_p = jnp.pad(hs[:, : t - 1], ((0, 0), (0, pad), (0, 0)))
    nxt_p = jnp.pad(nxt, ((0, 0), (0, pad)))
    hc_p = (None if hc is None else
            jnp.pad(hc[:, : t - 1], ((0, 0), (0, pad), (0, 0))))

    def one(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, 1)
        lg = _head(params, sl(hs_p), cfg)
        best = jnp.max(lg, axis=-1)
        if hc_p is None:
            tok = sl(nxt_p)
        else:
            tok = jnp.argmax(_head(params, sl(hc_p), cfg), axis=-1)
        at = jnp.take_along_axis(lg, tok[..., None], axis=-1)[..., 0]
        return best - at

    out = jax.lax.map(one, jnp.arange(nch))            # (nch, B, chunk)
    out = jnp.moveaxis(out, 0, 1).reshape(b, nch * chunk)
    return out[:, : t - 1]
