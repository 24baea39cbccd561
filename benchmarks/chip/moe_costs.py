"""Operations and bytes from shapes for a hybrid LM with MoE
feed-forwards (LFM2, ``lfm2_moe``): the arithmetic that ``step_mfu.moe``
and ``step_hbm_share.moe`` divide by. Reads the configuration dicts of
``benchmarks/chip/configs`` and nothing of the program.

Layers: ``layer_types`` names each operator, a gated short conv
(in_proj d -> 3d, a depthwise kernel of ``conv_L_cache`` taps, out_proj
d -> d) or GQA with per-head q/k norms; the first ``num_dense_layers``
feed-forwards are dense SwiGLU of ``intermediate_size``, the rest a
float32 router and ``num_experts`` SwiGLU experts of
``moe_intermediate_size``, ``num_experts_per_tok`` of them per token.

  active MACs per token = projections + conv taps + the router + the
                          routed experts (top k, not all) + head +
                          attention over the context
  bytes per step        = every non-expert parameter + the experts hit +
                          the live KV of the attention layers
"""
from __future__ import annotations

CHUNK = 128          # packing chunk along the reduction axis
VOCAB_PAD = 256      # served vocabularies are padded to this multiple


def _round_up(v: int, m: int) -> int:
    return v + (-v) % m


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kinds = cfg["layer_types"]
    n_dense = cfg["num_dense_layers"]
    return {"d": d, "h": h, "hk": cfg["num_key_value_heads"], "dh": d // h,
            "ff": cfg["intermediate_size"],
            "eff": cfg["moe_intermediate_size"],
            "experts": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "taps": cfg["conv_L_cache"],
            "conv": sum(op == "conv" for op in kinds),
            "attn": sum(op == "full_attention" for op in kinds),
            "dense": n_dense, "moe": len(kinds) - n_dense,
            "vocab": cfg["vocab_size"],
            "vocab_pad": _round_up(cfg["vocab_size"], VOCAB_PAD)}


def _fbytes(cfg: dict) -> int:
    return {"float32": 4, "bfloat16": 2}[cfg["serving"]["param_dtype"]]


def _packed(cfg: dict, d_in: int, d_out: int) -> int:
    """A W-bit packed projection with its float32 per-channel scales."""
    w_bits = cfg["serving"]["w_bits"]
    return _round_up(d_in, CHUNK) * w_bits // 8 * d_out + 4 * d_out


def shapes(cfg: dict) -> dict:
    """(d_in, d_out) of each layer kind's projections."""
    m = dims(cfg)
    d, hd, kvd = m["d"], m["h"] * m["dh"], m["hk"] * m["dh"]
    return {"conv": [(d, 3 * d), (d, d)],
            "attn": [(d, hd), (d, kvd), (d, kvd), (hd, d)],
            "dense": [(d, m["ff"]), (d, m["ff"]), (m["ff"], d)],
            "expert": [(d, m["eff"]), (d, m["eff"]), (m["eff"], d)]}


def expert_bytes(cfg: dict) -> int:
    """One expert's packed projections and scales."""
    return sum(_packed(cfg, i, o) for i, o in shapes(cfg)["expert"])


def non_expert_bytes(cfg: dict) -> int:
    """Every served parameter that is not an expert's: packed projections
    with float32 scales; float32 norms, conv kernels, routers, expert
    biases and the tied embedding."""
    m, sh, fb = dims(cfg), shapes(cfg), _fbytes(cfg)
    d = m["d"]
    conv = (sum(_packed(cfg, i, o) for i, o in sh["conv"])
            + m["taps"] * d * fb + d * fb)
    attn = (sum(_packed(cfg, i, o) for i, o in sh["attn"])
            + 2 * m["dh"] * fb + d * fb)
    dense = sum(_packed(cfg, i, o) for i, o in sh["dense"]) + d * fb
    router = (d * m["experts"] + m["experts"]) * fb + d * fb
    total = (m["conv"] * conv + m["attn"] * attn + m["dense"] * dense
             + m["moe"] * router + d * fb)                # + final norm
    return total + m["vocab_pad"] * d * fb                 # tied embedding


def param_bytes(cfg: dict) -> int:
    """Bytes of the whole served tree."""
    m = dims(cfg)
    return non_expert_bytes(cfg) + m["moe"] * m["experts"] * expert_bytes(
        cfg)


def kv_bytes_per_position(cfg: dict) -> int:
    m = dims(cfg)
    kv = {"bfloat16": 2, "float32": 4, "int8": 1}[cfg["serving"]["kv_dtype"]]
    return m["attn"] * 2 * m["hk"] * m["dh"] * kv


def step_bytes(cfg: dict, live_positions: float, experts_hit: float) -> float:
    """Bytes one decode step must stream: every non-expert parameter
    once, the ``experts_hit`` experts (summed over the MoE layers) once,
    and the KV entries of the positions the batch attends to
    (``live_positions`` summed over the occupied slots)."""
    return (non_expert_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + live_positions * kv_bytes_per_position(cfg))


def macs_per_token(cfg: dict, context: float) -> float:
    """Active MACs for one token at a context of ``context`` positions
    (its own included): the routed experts only."""
    m, sh = dims(cfg), shapes(cfg)
    mm = lambda kind: sum(i * o for i, o in sh[kind])
    per_moe = m["d"] * m["experts"] + m["k"] * mm("expert")
    attn = m["attn"] * 2 * m["h"] * m["dh"] * context
    return (m["conv"] * (mm("conv") + m["taps"] * m["d"])
            + m["attn"] * mm("attn") + m["dense"] * mm("dense")
            + m["moe"] * per_moe + m["d"] * m["vocab"] + attn)


def ops_per_token(cfg: dict, context: float) -> float:
    return 2.0 * macs_per_token(cfg, context)
