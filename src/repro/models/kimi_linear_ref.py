"""Plain float32 Kimi Linear forward pass: the reference that
`repro.models.lm.DecoderLM` is tested against for `kimi-linear-48b-a3b`.

Written from the Kimi Linear report (arXiv:2510.26692: Kimi Delta
Attention, the 3:1 KDA:MLA hybrid), the fla-org `KimiDeltaAttention` layer
and the published `config.json`, in straightforward `jax.numpy` at float32
under `jax.default_matmul_precision("highest")`:

* KDA token by token, straight from the recurrence
      S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T,
      o_t = S_t^T q_t,
  with q, k, v = SiLU(causal depthwise conv_4(W x)), q and k L2-normalised
  per head and q scaled by head_dim^-1/2, b_t = sigmoid(W_b x), the
  per-channel log-decay g_t = -exp(A_log) * softplus(W_f_up W_f_down x +
  dt_bias), and y = W_o [RMSNorm_head(o) * sigmoid(W_g_up W_g_down x + b)];
  no chunking, no cache;
* MLA expanded (every head's keys and values up-projected from the latent,
  no weight absorption), the 64-wide decoupled slice unrotated
  (`mla_use_nope`);
* a sigmoid router whose top-k gates are renormalised and scaled by
  `routed_scaling_factor`, every routed expert held computed densely and
  masked by its gate, the shared expert, the dense first layer, the final
  norm and the head.

It imports nothing of `repro.models.layers`; it reads `DecoderLM`'s
parameter pytree, so it computes the experts that pytree holds: given a
slice of the expert weights (one chip's share) and the full router, it
computes that share's part of each MoE layer, as the model does.

Departures from the published model, shared with `DecoderLM`:

* q, k and v come from one fused projection and one conv over the
  concatenated channels: the same map as three of each.
* The decay and output gates' rank (128, the head width) and the initial
  `dt_bias` (0) are read, not published.
* No selection-only router bias (DeepSeek-V3's e_score_correction_bias):
  the config has none.
* The vocabulary is padded to a multiple of 256 and the padded logits are
  masked (163840 already is one).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig

__all__ = ["forward", "kda", "kda_recurrence", "moe"]

Params = Dict[str, Any]


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _mlp(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def kda_recurrence(q, k, v, g, beta):
    """The KDA recurrence token by token from a zero state: q, k, g
    [B,S,H,dk], v [B,S,H,dv], beta [B,S,H] -> (o [B,S,H,dv], S_last)."""
    b, _, h, dk = q.shape

    def token(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[..., None]
        err = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, err)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    last, o = jax.lax.scan(token,
                           jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                           tuple(jnp.moveaxis(t, 1, 0)
                                 for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def kda(p: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """One KDA mixer over x [B, S, D], token by token from a zero state."""
    h, d = cfg.kda.num_heads, cfg.kda.head_dim
    b, s, _ = x.shape
    qkv = x @ p["wqkv"]
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * p["conv_w"][i] for i in range(taps))
    q, k, v = (t.reshape(b, s, h, d)
               for t in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q = _l2norm(q) / math.sqrt(d)
    k = _l2norm(k)
    f = ((x @ p["wf_down"]) @ p["wf_up"] + p["dt_bias"]).reshape(b, s, h, d)
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(x @ p["wb"])
    o, _ = kda_recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid((x @ p["wg_down"]) @ p["wg_up"] + p["g_bias"])
    o = _rms_norm(o, p["o_norm"], cfg.norm_eps).reshape(b, s, h * d)
    return (o * gate) @ p["wo"]


def _mla(p, x, cfg: ArchConfig):
    m, h = cfg.mla, cfg.num_heads
    b, s, _ = x.shape
    nope, rope_d, lora = m.qk_nope_head_dim, m.qk_rope_head_dim, \
        m.kv_lora_rank
    q = (x @ p["wq"]).reshape(b, s, h, nope + rope_d)
    ckv = x @ p["wdkv"]
    k_pe = ckv[..., lora:]                     # one unrotated key, all heads
    kv = (_rms_norm(ckv[..., :lora], p["kv_norm"], cfg.norm_eps)
          @ p["wukv"]).reshape(b, s, h, nope + m.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q[..., nope:], k_pe))
    scores = scores / math.sqrt(nope + rope_d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
    return o @ p["wo"]


def moe(p: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Routed experts held in `p` plus the shared expert, x [B, S, D]."""
    m = cfg.moe
    scores = jax.nn.sigmoid(x @ p["router"])               # [B, S, E]
    weight, idx = jax.lax.top_k(scores, m.top_k)
    if m.norm_topk_prob:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * m.routed_scale
    gates = (jax.nn.one_hot(idx, scores.shape[-1])
             * weight[..., None]).sum(-2)
    y = jnp.zeros_like(x)
    for e in range(p["we1"].shape[0]):
        expert = {"w1": p["we1"][e], "w3": p["we3"][e], "w2": p["we2"][e]}
        y = y + gates[..., e:e + 1] * _mlp(expert, x)
    if "shared" in p:
        y = y + _mlp(p["shared"], x)
    return y


def _layer_params(params: Params) -> List[Params]:
    """`DecoderLM`'s parameters, one dict per layer, scan groups
    unstacked (a stacked group's norm scales have a leading axis)."""
    out: List[Params] = []
    for unit in params["groups"]:
        repeats = unit[0]["ln1"].shape[0] if unit[0]["ln1"].ndim == 2 else 0
        if not repeats:
            out.extend(unit)
            continue
        for r in range(repeats):
            out.extend(jax.tree.map(lambda t, r=r: t[r], p) for p in unit)
    return out


def forward(params: Params, tokens: jax.Array, cfg: ArchConfig
            ) -> jax.Array:
    """tokens [B, S] int -> logits [B, S, V padded] float32."""
    params = jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for p in _layer_params(params):
            h = _rms_norm(x, p["ln1"], cfg.norm_eps)
            x = x + (kda(p["kda"], h, cfg) if "kda" in p
                     else _mla(p["attn"], h, cfg))
            h = _rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + (moe(p["moe"], h, cfg) if "moe" in p
                     else _mlp(p["mlp"], h))
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x @ params["lm_head"]
    pad = jnp.arange(logits.shape[-1]) >= cfg.vocab_size
    return jnp.where(pad, -1e9, logits)
