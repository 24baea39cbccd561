"""Plain float32 reference of LFM2-8B-A1B (``lfm2_moe``) served at W{w}A{a}.

Follows LFM2's published modelling, written out here in ``jax.numpy``
without anything of the program:

  * block: ``h = x + op(operator_norm(x))``, ``out = h + ffn(ffn_norm(h))``;
    RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; the final norm
    (``embedding_norm``) before the tied head;
  * conv layers: ``B, C, x = split3(in_proj(x))``,
    ``y = out_proj(C * conv(B * x))`` with ``conv`` the full causal
    depthwise convolution of width ``conv_L_cache`` over the whole
    sequence (PyTorch's ``Conv1d(groups=d, padding=L-1)`` cut to the
    sequence), no biases;
  * attention layers: per-head RMSNorm on q and k, rotate-half RoPE,
    causal GQA, ``out_proj``; no biases;
  * feed-forwards: the first ``num_dense_layers`` a SwiGLU of
    ``intermediate_size``; the rest MoE: router scores
    ``s = sigmoid(x W_r)`` in float32, the top ``num_experts_per_tok``
    experts by ``s + expert_bias``, their weights ``s`` renormalised
    (+1e-6) and scaled by ``routed_scaling_factor``, each token summing
    its own experts' SwiGLU outputs (every expert is evaluated on every
    row and weighted by the token's routing weight, zero off its top k:
    plain per-token top-k, nothing dropped).

Departures from the published model, all the configuration's serving
arithmetic: weights are the chunk-planar packed codes, unpacked by this
file's own decoder and scaled per output channel by ``w_scale``;
before every projection (never the router) the activations are
quantized onto the symmetric ``a_bits`` grid of the static range
``a_absmax``, the integer product is exact in float32, then scaled back.
Everything else (norms, conv, RoPE, softmax, residual stream, router,
head) is float32 at ``default_matmul_precision("highest")``, with no
bf16 rounding anywhere and no KV cache or conv state: the whole sequence
runs at once.

Each layer is its own jitted call, so only one layer's weights are
unpacked at a time. ``variant`` lowers one precision for the control:
``{"a_bits": 4}``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 128
_A_MAX = {8: 127, 4: 7, 2: 1}       # symmetric activation grid


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


def _quant(x, a_bits, a_absmax):
    s = a_absmax / _A_MAX[a_bits]
    return jnp.clip(jnp.round(x / s), -_A_MAX[a_bits], _A_MAX[a_bits]), s


def _dense(p, x, q):
    """q = (w_bits, a_bits, a_absmax)."""
    xq, s = _quant(x, q[1], q[2])
    w = unpack(p["w_packed"], q[0])[: x.shape[-1]]
    return jnp.matmul(xq, w) * (p["w_scale"] * s)


def _swiglu(p, x, q):
    return _dense(p["wo"], jax.nn.silu(_dense(p["wg"], x, q))
                  * _dense(p["wi"], x, q), q)


@functools.partial(jax.jit, static_argnames=("eps", "q"))
def conv_layer(lp, x, *, eps, q):
    """x + out_proj(C * causal_conv(B * x)) over the whole sequence."""
    with jax.default_matmul_precision("highest"):
        c = lp["conv"]
        bcx = _dense(c["in_proj"], _rms(x, lp["ln1"]["scale"], eps), q)
        b, gate, xx = jnp.split(bcx, 3, axis=-1)
        u = b * xx
        k, t = c["conv"].shape[0], x.shape[1]
        up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(c["conv"][j] * up[:, j:j + t] for j in range(k))
        return x + _dense(c["out_proj"], gate * conv, q)


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


@functools.partial(jax.jit,
                   static_argnames=("eps", "q", "heads", "kv_heads", "theta"))
def attn_layer(lp, x, *, eps, q, heads, kv_heads, theta):
    with jax.default_matmul_precision("highest"):
        a = lp["attn"]
        b, t, d = x.shape
        dh, g = d // heads, heads // kv_heads
        hn = _rms(x, lp["ln1"]["scale"], eps)
        qh = _rms(_dense(a["wq"], hn, q).reshape(b, t, heads, dh),
                  a["q_norm"]["scale"], eps)
        kh = _rms(_dense(a["wk"], hn, q).reshape(b, t, kv_heads, dh),
                  a["k_norm"]["scale"], eps)
        vh = _dense(a["wv"], hn, q).reshape(b, t, kv_heads, dh)
        qh = _rope(qh, theta).reshape(b, t, kv_heads, g, dh)
        kh = _rope(kh, theta)
        sc = jnp.einsum("bthgd,bshd->bhgts", qh, kh) * (dh ** -0.5)
        sc = jnp.where(jnp.tril(jnp.ones((t, t), bool)), sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhgts,bshd->bthgd", pr, vh).reshape(b, t, d)
        return x + _dense(a["wo"], o, q)


@functools.partial(jax.jit, static_argnames=("eps", "q"))
def dense_ffn(lp, x, *, eps, q):
    with jax.default_matmul_precision("highest"):
        return x + _swiglu(lp["mlp"], _rms(x, lp["ln2"]["scale"], eps), q)


def route(m, x, top_k: int, norm_topk: bool, scale: float):
    """(..., E): each token's routing weight of every expert, zero off
    its top k."""
    s = jax.nn.sigmoid(jnp.matmul(x, m["router"]))
    _, idx = jax.lax.top_k(s + m["expert_bias"], top_k)
    pick = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32), -2)
    w = s * pick
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * scale


@functools.partial(jax.jit,
                   static_argnames=("eps", "q", "top_k", "norm_topk",
                                    "scale"))
def moe_ffn(lp, x, *, eps, q, top_k, norm_topk, scale):
    with jax.default_matmul_precision("highest"):
        m = lp["moe"]
        hn = _rms(x, lp["ln2"]["scale"], eps)
        w = route(m, hn, top_k, norm_topk, scale)

        def one(y, e):
            ex = jax.tree.map(lambda a: a[e], {k: m[k] for k in
                                               ("wi", "wg", "wo")})
            return y + w[..., e, None] * _swiglu(ex, hn, q), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            jnp.arange(w.shape[-1]))
        return x + y


def layer_kinds(cfg: dict):
    """(operator, ffn) of every layer, in order."""
    n_dense = cfg["num_dense_layers"]
    return [(op, "mlp" if i < n_dense else "moe")
            for i, op in enumerate(cfg["layer_types"])]


def _at(stack, i):
    return jax.tree.map(lambda a: a[i], stack)


def hidden_states(params, tokens, cfg: dict, variant: dict):
    """Final-norm hidden states (B, T, d) of ``tokens`` (B, T)."""
    srv = cfg["serving"]
    q = (srv["w_bits"], variant.get("a_bits", srv["a_bits"]),
         float(srv["a_absmax"]))
    eps = float(cfg["norm_eps"])
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    n = {"conv": 0, "full_attention": 0, "mlp": 0, "moe": 0}
    for op, ffn in layer_kinds(cfg):
        if op == "conv":
            x = conv_layer(_at(params["conv_layers"], n[op]), x, eps=eps,
                           q=q)
        else:
            x = attn_layer(_at(params["attn_layers"], n[op]), x, eps=eps,
                           q=q, heads=cfg["num_attention_heads"],
                           kv_heads=cfg["num_key_value_heads"],
                           theta=float(cfg["rope_theta"]))
        if ffn == "mlp":
            x = dense_ffn(_at(params["dense_ffn"], n[ffn]), x, eps=eps, q=q)
        else:
            x = moe_ffn(_at(params["moe_ffn"], n[ffn]), x, eps=eps, q=q,
                        top_k=cfg["num_experts_per_tok"],
                        norm_topk=bool(cfg["norm_topk_prob"]),
                        scale=float(cfg["routed_scaling_factor"]))
        n[op] += 1
        n[ffn] += 1
    return _rms(x, params["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("vocab",))
def _head(table, hs, vocab: int):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("btd,vd->btv", hs, table)[..., :vocab]


@functools.partial(jax.jit, static_argnames=("vocab", "chunk"))
def _gaps(table, hs, hc, tokens, *, vocab: int, chunk: int):
    """Per position p < T-1: best logit minus the logit of tokens[:, p+1]
    (``hc`` None), or of the token ``hc``'s logits put first."""
    b, t = tokens.shape
    nch = (t - 1 + chunk - 1) // chunk
    pad = nch * chunk - (t - 1)
    padded = lambda a: jnp.pad(a[:, : t - 1], ((0, 0), (0, pad))
                               + ((0, 0),) * (a.ndim - 2))
    hs_p, nxt_p = padded(hs), padded(tokens[:, 1:])
    hc_p = None if hc is None else padded(hc)

    def one(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, 1)
        with jax.default_matmul_precision("highest"):
            lg = jnp.einsum("btd,vd->btv", sl(hs_p), table)[..., :vocab]
            tok = (sl(nxt_p) if hc_p is None else jnp.argmax(
                jnp.einsum("btd,vd->btv", sl(hc_p), table)[..., :vocab],
                axis=-1))
        at = jnp.take_along_axis(lg, tok[..., None], axis=-1)[..., 0]
        return jnp.max(lg, axis=-1) - at

    out = jax.lax.map(one, jnp.arange(nch))            # (nch, B, chunk)
    return jnp.moveaxis(out, 0, 1).reshape(b, nch * chunk)[:, : t - 1]


def logits(params, tokens, cfg: dict, variant: dict = None):
    """(B, T, vocab) float32 logits of the whole sequence (tied head)."""
    hs = hidden_states(params, tokens, cfg, variant or {})
    return _head(params["embed"]["table"], hs, cfg["vocab_size"])


def gaps(params, tokens, cfg: dict, chunk: int = 128, control=None):
    """Per position p of ``tokens`` (B, T), the gap by which a token's
    reference logit lies below the reference's best logit at p: the
    served token tokens[:, p+1], or (with ``control``, a variant) the
    token the control's logits put first. Returns (B, T-1) float32."""
    hs = hidden_states(params, tokens, cfg, {})
    hc = None if control is None else hidden_states(params, tokens, cfg,
                                                    control)
    return _gaps(params["embed"]["table"], hs, hc, tokens,
                 vocab=cfg["vocab_size"], chunk=chunk)
