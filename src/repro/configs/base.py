"""Config dataclasses: model, quantization, parallelism, shapes.

Every assigned architecture file (src/repro/configs/<id>.py) builds a
ModelConfig with its exact published numbers plus a reduced smoke_config()
of the same family for CPU tests. Shape presets (train_4k / prefill_32k /
decode_32k / long_500k) are shared across LM archs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.deploy.policy import PrecisionPlan
from repro.nn.layers import QOFF, QuantConfig


@dataclasses.dataclass(frozen=True)
class MoeSpec:
    n_experts: int
    top_k: int
    d_ff: int                  # per-expert hidden
    capacity_factor: float = 1.25
    group_size: int = 1024
    shared_expert: bool = True
    # router: "softmax" (top-k of the softmax, unnormalised) | "sigmoid"
    # (LFM2: top-k of sigmoid + expert_bias, the bias choosing only)
    router: str = "softmax"
    expert_bias: bool = False
    norm_topk: bool = False     # renormalise the k weights (+1e-6)
    routed_scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                # lm | encdec | mamba | griffin
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0          # 0 -> d_model // n_heads
    act: str = "swiglu"
    norm: str = "rmsnorm"      # rmsnorm|layernorm|nonparam_ln|gemma_rmsnorm
    norm_eps: float = 1e-6
    qkv_bias: bool = False
    tie_embeddings: bool = True
    scale_embed: bool = False  # gemma family: embed * sqrt(d)
    rope_theta: float = 10000.0
    # sliding-window schedule: window size used on "local" layers; pattern
    # gives the repeating layer kinds, e.g. ("local",)*5 + ("global",) for
    # gemma3. Empty pattern -> all-global.
    window: int = 0
    pattern: Tuple[str, ...] = ()
    rope_theta_local: Optional[float] = None
    # MoE
    moe: Optional[MoeSpec] = None
    # hybrid stacks (LFM2): an explicit per-layer operator, "conv" (gated
    # short conv of width d_conv) or "full_attention"; empty -> every
    # layer is attention under `pattern`. With moe set, the first
    # n_dense_layers feed-forwards are dense (d_ff), the rest MoE.
    layer_types: Tuple[str, ...] = ()
    n_dense_layers: int = 0
    qk_norm: bool = False      # per-head RMSNorm on q and k before RoPE
    # vision cross-attn: one cross layer after every `cross_every` self
    # layers; n_layers counts BOTH kinds (llama-3.2-vision: 80 self+20 cross)
    cross_every: int = 0
    # enc-dec
    enc_layers: int = 0
    dec_layers: int = 0
    # mamba
    d_state: int = 128
    d_conv: int = 4            # also the hybrid short conv's width
    expand: int = 2
    headdim: int = 64
    ssd_chunk: int = 256
    # griffin (recurrentgemma): pattern handled via rnn_pattern
    lru_width: int = 0
    rnn_pattern: Tuple[str, ...] = ()  # e.g. ("rec","rec","attn")
    # quantization (the paper's technique). `quant` is the uniform/default
    # QuantConfig; `quant_plan` (mixed-precision deployment) overrides
    # {w_bits, a_bits, backend, a_absmax} per dense param path — see
    # repro/deploy/policy.py. Packed param shapes follow the resolved bits.
    quant: QuantConfig = QOFF
    quant_plan: Optional[PrecisionPlan] = None
    kv_quant_bits: int = 16
    # training
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    # modality frontend stub (audio/vlm): src embeddings length
    src_len: int = 0

    @property
    def head_dim_(self):
        return self.head_dim or (self.d_model // self.n_heads if self.n_heads else 0)

    def layer_kinds(self):
        """Expanded per-layer kind list for pattern-scheduled archs."""
        if not self.pattern:
            return ["global"] * self.n_layers
        reps = (self.n_layers + len(self.pattern) - 1) // len(self.pattern)
        return list((self.pattern * reps)[: self.n_layers])


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode

SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# archs whose attention is sub-quadratic enough for long_500k decode
# (SSM / hybrid / mostly-local); pure full-attention archs skip it
# (documented in DESIGN.md §Arch-applicability).
LONG_CONTEXT_OK = {"gemma3-1b", "recurrentgemma-9b", "mamba2-370m"}


def cells_for(arch_name: str):
    """The (arch x shape) cells this arch runs in the dry-run matrix."""
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and arch_name not in LONG_CONTEXT_OK:
            continue
        out.append(s)
    return out
