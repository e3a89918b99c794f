"""Architecture configuration schema for the model zoo.

One frozen dataclass describes every assigned architecture; the builders in
`repro.models.lm` / `repro.models.encdec` consume it.  Families:

  dense   — GQA decoder LM (qwen2*, mistral-nemo)
  moe     — mixture-of-experts decoder LM (olmoe, deepseek-v2-lite w/ MLA)
  hybrid  — RG-LRU + local attention (recurrentgemma)
  ssm     — xLSTM (mLSTM + sLSTM blocks)
  audio   — encoder-decoder with stubbed conv frontend (whisper)
  vlm     — decoder LM with stubbed ViT patch embeddings (internvl2)
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["MoEConfig", "MLAConfig", "KDAConfig", "ArchConfig"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                  # hidden width of each routed expert
    num_shared: int = 0            # shared (always-on) experts
    norm_topk_prob: bool = True    # olmoe normalizes; deepseek-v2 does not
    router_dtype: str = "float32"
    first_dense: int = 0           # leading dense layers (deepseek-v2)
    dense_d_ff: int = 0            # FF width of those dense layers
    scoring: str = "softmax"       # router scores: softmax | sigmoid
    routed_scale: float = 1.0      # routed_scaling_factor on the gates


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2)."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 0           # 0 = full-rank queries (V2-Lite)
    use_rope: bool = True          # False: the decoupled slice is unrotated


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """Kimi Delta Attention: a gated delta rule with per-channel decay
    (arXiv:2510.26692).  `layers` are the 1-based indices of the KDA
    layers; every other layer is full (MLA) attention."""

    num_heads: int
    head_dim: int                  # key and value width per head
    layers: Tuple[int, ...]
    gate_rank: int                 # rank of the decay and output gates
    conv_size: int = 4             # short causal conv on q, k and v


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False

    # block pattern cycled over layers; entries in
    # {"attn", "local_attn", "rglru", "mlstm", "slstm"}
    block_pattern: Tuple[str, ...] = ("attn",)
    local_window: int = 2048
    lru_width: int = 0             # 0 -> d_model
    conv1d_width: int = 4

    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    kda: Optional[KDAConfig] = None

    # encoder-decoder (audio family)
    encoder_layers: int = 0
    encoder_seq: int = 0           # fixed encoder length (stub frontend)

    # multimodal stub frontend
    frontend: str = "none"         # none | vit_stub | conv_stub
    num_patches: int = 0           # vlm: patch-embedding prefix length

    # capability flags
    sub_quadratic: bool = False    # constant-memory decode -> long_500k runs

    # ---------------------------------------------------------------- utils
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def layer_plan(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, ffn) of every layer.  The mixer is the block pattern's
        kind ("mla" for attention with `mla` set; "kda" at `kda.layers`),
        cycled from the first layer after the leading dense ones; the ffn
        is "dense", "moe" or "none" (mLSTM blocks have none)."""
        lead = self.moe.first_dense if self.moe is not None else 0
        kda = set(self.kda.layers) if self.kda is not None else ()
        p = self.block_pattern
        plan = []
        for i in range(self.num_layers):
            mixer = "attn" if i < lead else p[(i - lead) % len(p)]
            if i + 1 in kda:
                mixer = "kda"
            elif mixer == "attn" and self.mla is not None:
                mixer = "mla"
            if mixer == "mlstm":
                ffn = "none"
            elif (self.moe is not None and i >= lead
                  and mixer in ("attn", "mla", "kda")):
                ffn = "moe"
            else:
                ffn = "dense"
            plan.append((mixer, ffn))
        return tuple(plan)

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks + norms)."""
        d, hd = self.d_model, self.resolved_head_dim
        nh, nkv = self.num_heads, self.num_kv_heads
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        total = emb

        def attn_params() -> int:
            if self.mla is not None:
                m = self.mla
                qd = nh * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                p = d * qd                                   # W_q
                p += d * (m.kv_lora_rank + m.qk_rope_head_dim)   # W_dkv+W_kr
                p += m.kv_lora_rank * nh * (m.qk_nope_head_dim
                                            + m.v_head_dim)  # W_ukv
                p += nh * m.v_head_dim * d                   # W_o
                return p
            p = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
            if self.qkv_bias:
                p += nh * hd + 2 * nkv * hd
            return p

        def mlp_params(ff: int) -> int:
            # SwiGLU (3 matrices) except the GELU MLPs of the audio family
            return (2 if self.family == "audio" else 3) * d * ff

        def rglru_params() -> int:
            w = self.lru_width or d
            return 2 * d * w + w * d + self.conv1d_width * w + 2 * w

        def mlstm_params() -> int:
            up = 2 * d
            return d * up * 2 + up * d + 3 * up * (up // max(nh, 1)) // max(
                up // max(nh, 1), 1)  # approx q,k,v projections

        def kda_params() -> int:
            k = self.kda
            w, r = k.num_heads * k.head_dim, k.gate_rank
            return (3 * d * w + k.conv_size * 3 * w       # W_qkv, conv
                    + 2 * (d * r + r * w)                 # decay, out gates
                    + d * k.num_heads + w * d)            # W_beta, W_o

        for mixer, ffn in self.layer_plan():
            if mixer in ("attn", "mla", "local_attn"):
                total += attn_params()
            elif mixer == "kda":
                total += kda_params()
            elif mixer == "rglru":
                total += rglru_params()
            else:
                total += mlstm_params()
            if ffn == "moe":
                m = self.moe
                total += d * m.num_experts                 # router
                total += m.num_experts * mlp_params(m.d_expert)
                if m.num_shared:
                    total += mlp_params(m.d_expert * m.num_shared)
            elif ffn == "dense" and mixer != "slstm":
                total += mlp_params(self.moe.dense_d_ff if self.moe
                                    else self.d_ff)
        if self.encoder_layers:
            # encoder: self-attn + MLP; decoder layers already counted via
            # the layer plan get their cross-attention added here
            total += self.encoder_layers * (attn_params()
                                            + mlp_params(self.d_ff))
            total += self.num_layers * attn_params()      # cross-attn
        return total

    def encoder_param_count(self) -> int:
        """Parameters in the encoder stack only (enc-dec FLOP accounting)."""
        if not self.encoder_layers:
            return 0
        d, hd = self.d_model, self.resolved_head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd \
            + self.num_heads * hd * d
        mats = 2 if self.family == "audio" else 3
        return self.encoder_layers * (attn + mats * d * self.d_ff)
