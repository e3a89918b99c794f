"""kimi-linear-48b-a3b [moe] 27L d_model=2304, 20 KDA + 7 MLA layers (32
heads each), MoE 256e top-8 + 1 shared, vocab=163840 [arXiv:2510.26692;
hf moonshotai/Kimi-Linear-48B-A3B-Instruct config.json].

Layers (1-based, as `linear_attn_config` lists them): KDA at 1-3, 5-7, 9-11,
13-15, 17-19, 21-23, 25, 26; MLA at 4, 8, 12, 16, 20, 24, 27 — three KDA to
one MLA, the last period cut short.  KDA (Kimi Delta Attention) is a gated
delta rule with a per-channel decay over a [32, 128, 128] state per layer,
with a kernel-4 short conv on q, k and v (`layers.kda_*`).  MLA keeps the
512-d latent and the 64-d decoupled key, unrotated (`mla_use_nope`): no
RoPE anywhere.  Layer 1 (a KDA layer) has a dense FFN of width 9216; layers
2-27 route over 256 experts of width 1024 (top-8, sigmoid scores,
renormalised, x 2.446) plus one shared expert.

Read as assumed (the config does not fix them): the low-rank decay and
output gates have rank 128 (head_dim); the decay bias `dt_bias` starts at
0; no selection-only router bias (DeepSeek-V3's e_score_correction_bias)
is modelled.  As a DSE workload the routed experts are costed at balanced
load (`repro.frontend.lower`).
"""

from repro.models.config import ArchConfig, KDAConfig, MLAConfig, MoEConfig

KDA_LAYERS = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
              23, 25, 26)

ARCH = ArchConfig(
    name="kimi-linear-48b-a3b", family="moe",
    num_layers=27, d_model=2304, num_heads=32, num_kv_heads=32,
    d_ff=1024, vocab_size=163840, norm_eps=1e-5,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, use_rope=False),
    moe=MoEConfig(num_experts=256, top_k=8, d_expert=1024, num_shared=1,
                  norm_topk_prob=True, first_dense=1, dense_d_ff=9216,
                  scoring="sigmoid", routed_scale=2.446),
    kda=KDAConfig(num_heads=32, head_dim=128, layers=KDA_LAYERS,
                  conv_size=4, gate_rank=128),
)

SMOKE = ArchConfig(
    name="kimi-linear-48b-a3b-smoke", family="moe",
    num_layers=7, d_model=64, num_heads=4, num_kv_heads=4,
    d_ff=48, vocab_size=512, norm_eps=1e-5,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, use_rope=False),
    moe=MoEConfig(num_experts=8, top_k=2, d_expert=48, num_shared=1,
                  norm_topk_prob=True, first_dense=1, dense_d_ff=96,
                  scoring="sigmoid", routed_scale=2.446),
    kda=KDAConfig(num_heads=4, head_dim=16, layers=(1, 2, 3, 5, 6),
                  conv_size=4, gate_rank=16),
)
