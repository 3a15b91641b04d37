"""Central model/runtime configuration.

One ``ModelConfig`` dataclass covers all six assigned architecture
families (dense / moe / ssm / hybrid / audio / vlm). Family-specific
fields default to "off" so a dense config never sees MoE or SSM state.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Enumerations (plain strings to keep configs trivially serializable)
# ---------------------------------------------------------------------------

ARCH_TYPES = ("dense", "moe", "ssm", "hybrid", "audio", "vlm")

# Paper precisions: F16 baseline, Q8_0 and Q4_0 k-quant analogues.
PRECISIONS = ("f32", "bf16", "f16", "q8_0", "q4_0")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # --- identity -----------------------------------------------------
    name: str = "unnamed"
    arch_type: str = "dense"  # one of ARCH_TYPES
    source: str = ""          # citation, e.g. "[arXiv:2401.02954]"

    # --- transformer backbone ----------------------------------------
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4          # GQA; == num_heads → MHA, 1 → MQA
    head_dim: int = 0              # 0 → d_model // num_heads
    d_ff: int = 1024               # per-expert d_ff when MoE
    vocab_size: int = 1024
    max_seq_len: int = 131072
    qkv_bias: bool = False         # Qwen1.5 style
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    activation: str = "silu"       # "silu" (SwiGLU) | "gelu" (GeGLU/plain)
    glu: bool = True               # gated MLP (gate+up) vs plain up

    # --- attention variants -------------------------------------------
    sliding_window: int = 0        # 0 → full attention; >0 → window size
    # window applied only for long-context decode when `window_long_ctx`
    window_long_ctx: int = 4096    # window used when seq exceeds max_full_attn
    max_full_attn: int = 131072    # beyond this, dense archs switch to window

    # --- MoE -----------------------------------------------------------
    num_experts: int = 0           # 0 → dense FFN
    experts_per_token: int = 0     # top-k
    capacity_factor: float = 1.25
    num_shared_experts: int = 0    # always-on shared experts (Kimi K2 style)
    router_aux_weight: float = 0.01

    # --- SSM (Mamba-2 / SSD) -------------------------------------------
    ssm_state: int = 0             # N (state dim); 0 → no SSM
    ssm_expand: int = 2            # d_inner = expand * d_model
    ssm_head_dim: int = 64         # P
    ssm_chunk: int = 256           # SSD chunk length
    ssm_conv: int = 4              # short conv width

    # --- hybrid (RecurrentGemma) ----------------------------------------
    # block pattern, e.g. ("rglru", "rglru", "attn") repeated — 1:2 ratio
    hybrid_pattern: Tuple[str, ...] = ()
    rglru_width: int = 0           # lru width; 0 → d_model
    local_attn_window: int = 2048

    # --- encoder-decoder (audio) ----------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 4096    # stub frontend frames fed to encoder

    # --- multimodal stubs ------------------------------------------------
    num_prefix_embeddings: int = 0  # VLM patch embeddings prepended (stub)

    # --- execution strategy ----------------------------------------------
    quant_policy: str = "bf16"     # weights precision: bf16|q8_0|q4_0
    quant_group: int = 32          # k-quant group size along reduction dim
    # KV-cache precision (the other half of the decode bandwidth story:
    # the cache stream grows with context/batch while weights don't).
    # Groupwise-quantized int8 payload + per-(position, head, group)
    # scales stored as sibling cache leaves. No-op for recurrent
    # families (ssm/hybrid): their state is small and
    # precision-sensitive, see Model.kv_quant_effective().
    kv_quant: str = "bf16"         # cache precision: bf16|q8_0|q4_0
    use_pallas: bool = False       # use Pallas kernels (interpret on CPU)
    # Kernel backend: one switch for the whole fused-dequant path
    # (quant_matmul for the layer GEMVs and a quantized lm_head, and the
    # quantized-KV decode-attention kernel). "" (default) derives from
    # use_pallas for backwards compatibility; an explicit "pallas"/"xla"
    # wins and rewrites use_pallas to match, so call sites keep reading
    # cfg.use_pallas.
    kernels: str = ""              # ""|"xla"|"pallas"
    remat: bool = True             # activation checkpointing per layer
    # Cost-calibration mode (launch/dryrun.py): python-loop the layer
    # stack and unroll inner scans so XLA cost_analysis counts every
    # iteration (while-loop bodies are otherwise counted once).
    unroll_scans: bool = False
    attn_block: int = 512          # chunked-attention q/kv block size

    # --- numerics ---------------------------------------------------------
    dtype: str = "bf16"            # activation dtype
    param_dtype: str = "bf16"

    # -------------------------------------------------------------------
    def __post_init__(self):
        assert self.arch_type in ARCH_TYPES, self.arch_type
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        # kernels is the one public switch; reconcile with the legacy
        # use_pallas bool (kernels wins when set, derives otherwise)
        if self.kernels == "":
            object.__setattr__(self, "kernels",
                               "pallas" if self.use_pallas else "xla")
        elif self.kernels in ("xla", "pallas"):
            object.__setattr__(self, "use_pallas", self.kernels == "pallas")
        else:
            raise ValueError(
                f"kernels must be '', 'xla' or 'pallas', got "
                f"{self.kernels!r}")

    # --- derived quantities ----------------------------------------------
    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so the embedding/lm_head
        shard evenly on any mesh axis (standard practice; mamba's 50280
        and seamless's 256206 don't divide 16). Padded logits classes
        are trained down like any other unused token."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    def layer_pattern(self) -> Tuple[str, ...]:
        """Per-layer block kinds, length == num_layers."""
        if self.arch_type == "ssm":
            return ("ssm",) * self.num_layers
        if self.arch_type == "hybrid":
            pat = self.hybrid_pattern or ("rglru", "rglru", "attn")
            return tuple(pat[i % len(pat)] for i in range(self.num_layers))
        return ("attn",) * self.num_layers

    # --- parameter counting (for roofline MODEL_FLOPS = 6·N·D) -----------
    def param_count(self, active_only: bool = False) -> int:
        """Total (or active, for MoE) parameter count, embeddings included."""
        D, H = self.d_model, self.head_dim
        n = self.vocab_size * D  # embeddings
        if not self.tie_embeddings:
            n += self.vocab_size * D  # lm head
        per_layer = 0
        for kind in self.layer_pattern():
            if kind == "attn":
                attn = D * self.q_dim + 2 * D * self.kv_dim + self.q_dim * D
                if self.qkv_bias:
                    attn += self.q_dim + 2 * self.kv_dim
                per_layer += attn + 2 * D  # + norms
                per_layer += self._ffn_params(active_only)
            elif kind == "rglru":
                w = self.rglru_width or D
                # input/gate proj + recurrent diag params + out proj
                per_layer += 2 * D * w + 4 * w + w * D + 2 * D
                per_layer += self._ffn_params(active_only)
            elif kind == "ssm":
                di, N, nh = self.d_inner, self.ssm_state, self.ssm_heads
                # in_proj → [z, x, B, C, dt]
                in_proj = D * (2 * di + 2 * N + nh)
                out_proj = di * D
                conv = self.ssm_conv * (di + 2 * N)
                per_layer += in_proj + out_proj + conv + nh * 2 + 2 * D
        n += per_layer
        if self.is_encoder_decoder:
            # encoder layers: self-attn + ffn; decoder already counted above,
            # add cross-attention per decoder layer
            enc = self.num_encoder_layers * (
                D * self.q_dim + 2 * D * self.kv_dim + self.q_dim * D
                + self._ffn_params(active_only) + 2 * D)
            cross = self.num_layers * (
                D * self.q_dim + 2 * D * self.kv_dim + self.q_dim * D + D)
            n += enc + cross
        return n

    def _ffn_params(self, active_only: bool) -> int:
        D, F = self.d_model, self.d_ff
        if F == 0:
            return 0
        dense_ffn = (3 if self.glu else 2) * D * F
        if not self.is_moe:
            return dense_ffn
        k = self.experts_per_token if active_only else self.num_experts
        shared = self.num_shared_experts * dense_ffn
        router = D * self.num_experts
        return k * dense_ffn + shared + router


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def reduced(cfg: ModelConfig, **over) -> ModelConfig:
    """Reduced variant of the same family for CPU smoke tests."""
    base = dict(
        num_layers=2,
        d_model=min(cfg.d_model, 128),
        num_heads=min(cfg.num_heads, 4),
        num_kv_heads=min(cfg.num_kv_heads, 2),
        head_dim=32,
        d_ff=min(cfg.d_ff, 256) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        max_seq_len=512,
        remat=False,
    )
    if cfg.is_moe:
        base["num_experts"] = min(cfg.num_experts, 4)
        base["experts_per_token"] = min(cfg.experts_per_token, 2)
        base["num_shared_experts"] = min(cfg.num_shared_experts, 1)
    if cfg.arch_type == "ssm":
        base["d_model"] = 128
        base["ssm_state"] = min(cfg.ssm_state, 16)
        base["ssm_head_dim"] = 32
        base["ssm_chunk"] = 64
    if cfg.arch_type == "hybrid":
        base["rglru_width"] = 0
        base["local_attn_window"] = 64
        base["num_layers"] = 3  # one full rglru-rglru-attn pattern
    if cfg.is_encoder_decoder:
        base["num_encoder_layers"] = 2
        base["encoder_seq_len"] = 64
    if cfg.num_prefix_embeddings:
        base["num_prefix_embeddings"] = 16
    # GQA ratio sanity: kv must divide heads
    if base["num_heads"] % max(base["num_kv_heads"], 1):
        base["num_kv_heads"] = 1
    base.update(over)
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **base)
