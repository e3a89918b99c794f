"""Plain float32 DeepSeek-V2 forward pass: the reference that
`repro.models.lm.DecoderLM` is tested against for `deepseek-v2-lite-16b`.

Written from the DeepSeek-V2 paper (arXiv:2405.04434: §2.1 multi-head
latent attention, §2.2 DeepSeekMoE) and the published `config.json`, in
straightforward `jax.numpy` at float32 under
`jax.default_matmul_precision("highest")`: expanded MLA (every head's keys
and values up-projected from the latent, no cache, no weight absorption),
a softmax router whose top-k weights are not renormalised
(`norm_topk_prob` false, `routed_scaling_factor` 1), every routed expert
computed densely and masked by its gate, the shared experts, the dense
first layer, the final norm and the head.  It imports nothing of
`repro.models.layers`; it reads `DecoderLM`'s parameter pytree.

Departures from the published model, shared with `DecoderLM`:

* RoPE rotates halves of the 64 decoupled dimensions; the published code
  rotates interleaved pairs, the same map up to a fixed permutation of
  the rope columns of `wq` and `wdkv`.
* YaRN `rope_scaling` (factor 40, mscale 0.707: scaled frequencies and a
  softmax scale multiplied by mscale^2) is not applied.
* The vocabulary is padded to a multiple of 256 and the padded logits are
  masked (102400 already is one).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig

__all__ = ["forward", "moe"]

Params = Dict[str, Any]


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE over the last axis of x [B, S, ..., d] at
    positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((s,) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def _mla(p, x, cfg: ArchConfig):
    m, h = cfg.mla, cfg.num_heads
    b, s, _ = x.shape
    nope, rope_d, lora = m.qk_nope_head_dim, m.qk_rope_head_dim, \
        m.kv_lora_rank
    q = (x @ p["wq"]).reshape(b, s, h, nope + rope_d)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cfg.rope_theta)
    ckv = x @ p["wdkv"]
    k_pe = _rope(ckv[..., lora:], cfg.rope_theta)      # one key for all heads
    kv = (_rms_norm(ckv[..., :lora], p["kv_norm"], cfg.norm_eps)
          @ p["wukv"]).reshape(b, s, h, nope + m.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe))
    scores = scores / math.sqrt(nope + rope_d)          # full qk head dim
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
    return o @ p["wo"]


def moe(p: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Routed plus shared experts of one MoE layer, x [B, S, D]."""
    m = cfg.moe
    probs = jax.nn.softmax(x @ p["router"], axis=-1)      # [B, S, E]
    weight, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        weight = weight / weight.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(idx, m.num_experts)
             * weight[..., None]).sum(-2)                 # [B, S, E]
    y = jnp.zeros_like(x)
    for e in range(m.num_experts):
        expert = {"w1": p["we1"][e], "w3": p["we3"][e], "w2": p["we2"][e]}
        y = y + gates[..., e:e + 1] * _mlp(expert, x)
    if "shared" in p:
        y = y + _mlp(p["shared"], x)
    return y


def _layer_params(params: Params, cfg: ArchConfig) -> List[Params]:
    """`DecoderLM`'s parameters, one dict per layer: the leading dense
    layers, then the MoE layers unstacked from their scan group."""
    dense, (unit,) = params["groups"]
    n = cfg.num_layers - cfg.moe.first_dense
    if n == 1:
        return list(dense) + [unit]
    return list(dense) + [jax.tree.map(lambda t, i=i: t[i], unit)
                          for i in range(n)]


def forward(params: Params, tokens: jax.Array, cfg: ArchConfig
            ) -> jax.Array:
    """tokens [B, S] int -> logits [B, S, V padded] float32."""
    params = jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for p in _layer_params(params, cfg):
            x = x + _mla(p["attn"], _rms_norm(x, p["ln1"], cfg.norm_eps),
                         cfg)
            h = _rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + (moe(p["moe"], h, cfg) if "moe" in p
                     else _mlp(p["mlp"], h))
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x @ params["lm_head"]
    pad = jnp.arange(logits.shape[-1]) >= cfg.vocab_size
    return jnp.where(pad, -1e9, logits)
