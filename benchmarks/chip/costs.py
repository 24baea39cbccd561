"""Operations and bytes from shapes: the arithmetic the rooflines and
utilizations divide by. Every function reads the configuration dicts of
``benchmarks/chip/configs`` and nothing of the program.

LM (a dense GQA decoder with a tied or untied head):
  MACs per token  = dense projections + head + attention over the context
  bytes per step  = every parameter at its served width + the live KV cache
CNN (a graph of convs, adds, pools and a linear head):
  MACs per image  = sum over conv / linear layers of out_elems * taps * cin
"""
from __future__ import annotations

CHUNK = 128          # packing chunk along the reduction axis
VOCAB_PAD = 256      # served vocabularies are padded to this multiple


def _round_up(v: int, m: int) -> int:
    return v + (-v) % m


# ------------------------------------------------------------------ LM ---

def lm_dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    return {"d": d, "ff": cfg["intermediate_size"], "h": h,
            "hk": cfg["num_key_value_heads"], "dh": dh,
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "vocab_pad": _round_up(cfg["vocab_size"], VOCAB_PAD),
            "tied": bool(cfg.get("tie_word_embeddings", False))}


def lm_dense_shapes(cfg: dict) -> list:
    """(name, d_in, d_out, bias) of one decoder layer's projections."""
    m = lm_dims(cfg)
    d, h, hk, dh, ff = m["d"], m["h"], m["hk"], m["dh"], m["ff"]
    return [("wq", d, h * dh, True), ("wk", d, hk * dh, True),
            ("wv", d, hk * dh, True), ("wo", h * dh, d, False),
            ("wi", d, ff, False), ("wg", d, ff, False),
            ("mlp_wo", ff, d, False)]


def lm_macs_per_token(cfg: dict, context: float) -> float:
    """MACs for one token at a context of ``context`` positions (the
    token's own included): projections, head, QK^T and PV."""
    m = lm_dims(cfg)
    dense = sum(i * o for _, i, o, _ in lm_dense_shapes(cfg)) * m["layers"]
    head = m["d"] * m["vocab"]
    attn = m["layers"] * 2 * m["h"] * m["dh"] * context
    return dense + head + attn


def lm_ops_per_token(cfg: dict, context: float) -> float:
    return 2.0 * lm_macs_per_token(cfg, context)


def lm_param_bytes(cfg: dict) -> int:
    """Bytes of the served parameter tree: packed w_bits containers with
    float32 per-channel scales, float32 biases, norms and embedding."""
    m = lm_dims(cfg)
    srv = cfg["serving"]
    w_bits = srv["w_bits"]
    fbytes = {"float32": 4, "bfloat16": 2}[srv["param_dtype"]]
    per_layer = 2 * m["d"] * fbytes                      # two norms
    for _, d_in, d_out, bias in lm_dense_shapes(cfg):
        per_layer += _round_up(d_in, CHUNK) * w_bits // 8 * d_out
        per_layer += d_out * 4                           # w_scale
        if bias:
            per_layer += d_out * fbytes
    total = per_layer * m["layers"] + m["d"] * fbytes    # + final norm
    total += m["vocab_pad"] * m["d"] * fbytes            # embedding
    if not m["tied"]:
        total += (_round_up(m["d"], CHUNK) * w_bits // 8 * m["vocab_pad"]
                  + m["vocab_pad"] * 4)
    return total


def lm_kv_bytes_per_position(cfg: dict) -> int:
    m = lm_dims(cfg)
    kv = {"bfloat16": 2, "float32": 4, "int8": 1}[cfg["serving"]["kv_dtype"]]
    return m["layers"] * 2 * m["hk"] * m["dh"] * kv


def lm_step_bytes(cfg: dict, live_positions: float) -> float:
    """Bytes one decode step must stream: every parameter once, plus the
    KV entries of the positions the batch attends to (``live_positions``
    summed over the occupied slots)."""
    return lm_param_bytes(cfg) + live_positions * lm_kv_bytes_per_position(
        cfg)


# ----------------------------------------------------------------- CNN ---

def cnn_layer_shapes(cfg: dict) -> list:
    """Walk the config's layer list: one dict per layer with its input
    and output (h, w, c). Mirrors the geometry every conv framework
    uses: out = (in + 2 pad - k) // stride + 1."""
    h, w = cfg["in_hw"]
    stream = (h, w, cfg["in_ch"])
    edges = {}
    out = []
    for L in cfg["layers"]:
        src = edges[L["input_from"]] if L.get("input_from") else stream
        ih, iw, c = src
        kind = L["kind"]
        if kind == "conv":
            k, s, p = L["k"], L["stride"], L["pad"]
            dst = ((ih + 2 * p - k) // s + 1, (iw + 2 * p - k) // s + 1,
                   L["cout"])
        elif kind == "add":
            dst = src
        elif kind == "avgpool_global":
            dst = (0, 0, c)
        elif kind == "linear":
            dst = (0, 0, L["cout"])
        else:
            raise ValueError(f"{L['path']}: unknown kind {kind!r}")
        out.append({"layer": L, "in": src, "out": dst})
        if L.get("save_as"):
            edges[L["save_as"]] = dst
        if not L.get("branch"):
            stream = dst
    return out


def layer_macs(t: dict) -> int:
    """MACs per image for one traced layer (0 for pool/add); the count of
    ``benchmarks/e2e_networks.py::_layer_macs``."""
    L, (_, _, c), (oh, ow, oc) = t["layer"], t["in"], t["out"]
    if L["kind"] == "conv":
        return oh * ow * oc * L["k"] * L["k"] * c
    if L["kind"] == "linear":
        return c * L["cout"]
    return 0


def cnn_ops_per_image(cfg: dict) -> int:
    return 2 * sum(layer_macs(t) for t in cnn_layer_shapes(cfg))


def qconv_call_cost(t: dict, w_bits: int, batch: int) -> tuple:
    """(ops, bytes) of one conv layer over ``batch`` images at its true
    widths: int8 activations in and out, the ``w_bits`` weights and the
    three int32 epilogue vectors. A least count: a kernel that pads
    channels moves and multiplies more."""
    L, (ih, iw, c), (oh, ow, oc) = t["layer"], t["in"], t["out"]
    k = L["k"]
    ops = 2 * batch * oh * ow * oc * k * k * c
    w = k * k * c * oc * w_bits // 8
    nbytes = batch * ih * iw * c + batch * oh * ow * oc + w + 3 * oc * 4
    return ops, nbytes


def qconv_least_seconds(cfg: dict, batch: int, peaks: dict) -> dict:
    """{(oh, ow): least seconds of one conv call with that output size},
    the mean over the conv layers of that size: per layer the larger of
    its ops over the int8 peak and its bytes over the HBM peak. A trace
    tells the calls apart only by shape, and layers of one output size
    share it."""
    by_size = {}
    for t in cnn_layer_shapes(cfg):
        if t["layer"]["kind"] != "conv":
            continue
        ops, nbytes = qconv_call_cost(t, cfg["plan"][t["layer"]["path"]],
                                      batch)
        by_size.setdefault(t["out"][:2], []).append(
            max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"]))
    return {k: sum(v) / len(v) for k, v in by_size.items()}
