"""Model-zoo layers: GQA/MLA attention, SwiGLU/GELU MLPs, dropless
token-choice MoE, RG-LRU recurrent blocks, mLSTM/sLSTM blocks, KDA (Kimi
Delta Attention) linear attention, local (sliding-window) attention — all
as pure functions over parameter pytrees.

Conventions
-----------
* Parameters are declared as `Spec` trees (shape + logical axes + init) so
  the same declaration serves three purposes: random init (smoke tests),
  `jax.eval_shape` stand-ins (dry-run), and NamedSharding derivation.
* Mixed precision: parameters fp32, activations bf16, matmul accumulation
  fp32 (`preferred_element_type`), softmax/norm/gate math fp32.
* Attention is written in the *grouped* GQA form (no KV head repetition) so
  decode-time KV caches stay at `num_kv_heads` width.
* Long-sequence attention uses a blocked online-softmax formulation (the
  pure-jnp reference of the Pallas flash kernel in `repro.kernels`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.distributed.sharding import AxisRules

Params = Any
PyTree = Any

__all__ = ["Runtime", "Spec", "init_params", "spec_shapes", "spec_axes"]


# ============================================================ runtime/context

@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution-space knobs threaded through every layer.

    These are the TPU analogues of the paper's Table 2 design variables and
    are mutated by `core/autotune.py`.
    """

    mesh: Optional[Mesh] = None
    rules: Optional[AxisRules] = None
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_pallas: bool = False
    attn_q_block: int = 512
    attn_kv_block: int = 1024
    mlstm_chunk: int = 256
    remat: str = "none"                 # none | full | dots
    kv_dtype: str = "bf16"              # bf16 | f8 (fp8 KV cache, serving)

    def shard(self, x: jax.Array, *axes: Optional[str]) -> jax.Array:
        if self.mesh is None or self.rules is None:
            return x
        spec = self.rules.spec(list(axes) + [None] * (x.ndim - len(axes)))
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))


# ================================================================ param specs

@dataclasses.dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    # normal | zeros | ones | rglru_a | kda_a_log | conv | small
    init: str = "normal"
    dtype: Optional[str] = None     # None -> param_dtype; "bf16" | "f32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def resolved_dtype(self, default):
        if self.dtype == "bf16":
            return jnp.bfloat16
        if self.dtype == "f32":
            return jnp.float32
        return default


def _is_spec(x) -> bool:
    return isinstance(x, Spec)


def init_params(specs: PyTree, key: jax.Array,
                param_dtype=jnp.float32) -> Params:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for spec, k in zip(leaves, keys):
        dt = spec.resolved_dtype(param_dtype)
        if spec.init == "zeros":
            p = jnp.zeros(spec.shape, dt)
        elif spec.init == "ones":
            p = jnp.ones(spec.shape, dt)
        elif spec.init == "rglru_a":
            # RG-LRU "Lambda" init: a in [0.9, 0.999] -> logit space
            u = jax.random.uniform(k, spec.shape, jnp.float32,
                                   0.9 ** 2, 0.999 ** 2)
            p = (jnp.log(u) - jnp.log1p(-u)).astype(dt)
        elif spec.init == "kda_a_log":
            # KDA decay rates: A = exp(A_log) ~ U(1, 16), as fla initialises
            p = jnp.log(jax.random.uniform(k, spec.shape, jnp.float32,
                                           1.0, 16.0)).astype(dt)
        elif spec.init == "conv":
            # a short conv's taps ~ U(+-1/sqrt(K)), PyTorch's Conv1d default
            bound = 1.0 / math.sqrt(spec.shape[0])
            p = jax.random.uniform(k, spec.shape, jnp.float32, -bound,
                                   bound).astype(dt)
        else:
            scale = 0.02 if spec.init == "normal" else 0.006
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            std = min(scale, 1.0 / math.sqrt(max(fan_in, 1)))
            p = (jax.random.normal(k, spec.shape, jnp.float32)
                 * std).astype(dt)
        out.append(p)
    return jax.tree.unflatten(treedef, out)


def spec_shapes(specs: PyTree, param_dtype=jnp.float32) -> PyTree:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.resolved_dtype(param_dtype)),
        specs, is_leaf=_is_spec)


def spec_axes(specs: PyTree) -> PyTree:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=_is_spec)


def stack_specs(specs: PyTree, n: int,
                axis_name: Optional[str] = "layers") -> PyTree:
    """Prepend a stacking dimension (for scan-over-layers parameters)."""
    return jax.tree.map(
        lambda s: Spec((n,) + s.shape, (axis_name,) + s.axes, s.init,
                       s.dtype),
        specs, is_leaf=_is_spec)


# ================================================================= norms/rope

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rope_cos_sin(positions: jax.Array, dim: int,
                 theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions [..., S] -> cos/sin [..., S, dim//2] (fp32)."""
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, S, H, hd] (rotate-half convention); cos/sin [B, S, hd//2]."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# ======================================================== blocked attention

def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B,Sq,KV,G,hd] x k [B,Skv,KV,hd] -> scores [B,KV,G,Sq,Skv] fp32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_values(p: jax.Array, v: jax.Array) -> jax.Array:
    """p [B,KV,G,Sq,Skv] x v [B,Skv,KV,hd] -> [B,Sq,KV,G,hd]."""
    return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, causal: bool, window: int = 0,
                      q_offset: int = 0,
                      kv_block: int = 1024,
                      kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Online-softmax attention over KV blocks (flash-attention reference).

    q [B, Sq, H, hd]; k, v [B, Skv, KV, hd].  `q_offset` is the absolute
    position of q[0] (for decode / chunked prefill).  `window > 0` limits
    attention to the last `window` positions.  `kv_len` (scalar) masks the
    tail of a statically-padded KV cache.

    Memory stays O(Sq x kv_block); the full [Sq, Skv] score matrix is never
    materialized.  This is the pure-jnp oracle of kernels/flash_attention.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    hd_v = v.shape[-1]                 # MLA: v head dim may differ from qk
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(B, Sq, KV, G, hd)

    nblk = -(-Skv // kv_block)
    pad = nblk * kv_block - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, kv_block, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, kv_block, KV, hd_v).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, blk):
        m, l, acc, j = carry
        kj, vj = blk
        s = _gqa_scores(qg, kj)                      # [B,KV,G,Sq,kb]
        kv_pos = j * kv_block + jnp.arange(kv_block)
        mask = jnp.ones((Sq, kv_block), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window:
            mask &= q_pos[:, None] - kv_pos[None, :] < window
        if kv_len is not None:
            mask &= (kv_pos < kv_len)[None, :]
        if pad:
            mask &= (kv_pos < Skv)[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + \
            _gqa_values(p, vj)
        return (m_new, l_new, acc_new, j + 1), None

    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KV, G, hd_v), jnp.float32)
    # checkpoint the block body: backward recomputes the O(Sq x kv_block)
    # score tile instead of saving one per block (the flash memory bound)
    (m, l, acc, _), _ = jax.lax.scan(jax.checkpoint(step),
                                     (m0, l0, acc0, 0), (kb, vb))
    l_t = jnp.maximum(l.transpose(0, 3, 1, 2)[..., None], 1e-30)
    out = (acc / l_t).reshape(B, Sq, H, hd_v)
    return out.astype(q.dtype)


def local_block_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          window: int,
                          rt: Optional["Runtime"] = None) -> jax.Array:
    """Sliding-window causal attention via block-banded computation.

    Exact for any window by letting each w-sized query block attend to its
    own and the previous ceil(window/w) blocks; O(S*window) compute instead
    of O(S^2).  Used by recurrentgemma's local-attention layers.
    """
    B, S, H, hd = q.shape
    w = min(window, S)
    nblk = -(-S // w)
    pad = nblk * w - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qb = (q * scale).reshape(B, nblk, w, KV, G, hd)
    kb = k.reshape(B, nblk, w, KV, hd)
    vb = v.reshape(B, nblk, w, KV, hd)
    if rt is not None:
        # shard the within-block query dim: robust for any block count
        qb = rt.shard(qb, "batch", None, "attn_seq")
    k_prev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], 1)
    v_prev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], 1)
    k2 = jnp.concatenate([k_prev, kb], axis=2)          # [B,n,2w,KV,hd]
    v2 = jnp.concatenate([v_prev, vb], axis=2)

    s = jnp.einsum("bnqkgd,bnskd->bnkgqs", qb, k2,
                   preferred_element_type=jnp.float32)
    qpos = jnp.arange(w)[:, None]
    kpos = jnp.arange(2 * w)[None, :] - w
    mask = (qpos >= kpos) & (qpos - kpos < window)
    first = jnp.arange(nblk) == 0                        # no prev block
    mask_f = mask & (kpos >= 0)
    m_all = jnp.where(first[:, None, None], mask_f[None], mask[None])
    s = jnp.where(m_all[None, :, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnkgqs,bnskd->bnqkgd", p.astype(v2.dtype), v2,
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, nblk * w, H, hd)[:, :S]
    return o.astype(q.dtype)


def kv_cache_write(cache: jax.Array, new: jax.Array, pos: jax.Array,
                   rt: "Runtime") -> jax.Array:
    """Write `new` [B, 1, ...] into `cache` [B, S, ...] at seq position
    `pos`.

    When the cache's seq dim is sharded (kv_seq -> model), a
    dynamic-update-slice at a runtime index makes GSPMD replicate the whole
    buffer ("involuntary full rematerialization") — for a 32k x 8-head
    cache that is gigabytes per layer.  The masked write below is a pure
    elementwise select, which partitions perfectly on every axis; its cost
    is one cache rewrite per step, which stays within the decode memory
    roofline.
    """
    sharded_seq = (rt.rules is not None and rt.mesh is not None
                   and rt.rules.get("kv_seq") is not None)
    if not sharded_seq:
        return jax.lax.dynamic_update_slice_in_dim(
            cache, new.astype(cache.dtype), pos, axis=1)
    iota = jnp.arange(cache.shape[1])
    mask = (iota == pos).reshape((1, -1) + (1,) * (cache.ndim - 2))
    return jnp.where(mask, new.astype(cache.dtype), cache)


# ========================================================== GQA attention

def gqa_specs(d: int, n_heads: int, n_kv: int, hd: int,
              qkv_bias: bool) -> Dict[str, Spec]:
    s = {
        "wq": Spec((d, n_heads * hd), ("embed", "qkv_fused")),
        "wk": Spec((d, n_kv * hd), ("embed", "qkv_fused")),
        "wv": Spec((d, n_kv * hd), ("embed", "qkv_fused")),
        "wo": Spec((n_heads * hd, d), ("qkv_fused", "embed")),
    }
    if qkv_bias:
        s["bq"] = Spec((n_heads * hd,), ("qkv_fused",), "zeros")
        s["bk"] = Spec((n_kv * hd,), ("qkv_fused",), "zeros")
        s["bv"] = Spec((n_kv * hd,), ("qkv_fused",), "zeros")
    return s


def gqa_project(p: Params, x: jax.Array, n_heads: int, n_kv: int, hd: int,
                rt: Runtime) -> Tuple[jax.Array, jax.Array, jax.Array]:
    cd = rt.compute_dtype
    B, S, _ = x.shape

    def proj(w, b, n):
        y = jnp.einsum("bsd,df->bsf", x, w.astype(cd),
                       preferred_element_type=jnp.float32)
        if b is not None:
            y = y + b.astype(jnp.float32)
        y = rt.shard(y.astype(cd), "batch", None, "qkv_fused")
        return y.reshape(B, S, n, hd)

    q = proj(p["wq"], p.get("bq"), n_heads)
    k = proj(p["wk"], p.get("bk"), n_kv)
    v = proj(p["wv"], p.get("bv"), n_kv)
    return q, k, v


def gqa_out(p: Params, attn: jax.Array, rt: Runtime) -> jax.Array:
    B, S, H, hd = attn.shape
    y = jnp.einsum("bsf,fd->bsd", attn.reshape(B, S, H * hd),
                   p["wo"].astype(rt.compute_dtype),
                   preferred_element_type=jnp.float32)
    return rt.shard(y.astype(rt.compute_dtype), "batch", None, "act_embed")


def gqa_attention_train(p: Params, x: jax.Array, *, n_heads: int, n_kv: int,
                        hd: int, rope_theta: float, rt: Runtime,
                        causal: bool = True, window: int = 0) -> jax.Array:
    B, S, _ = x.shape
    q, k, v = gqa_project(p, x, n_heads, n_kv, hd, rt)
    pos = jnp.arange(S)[None, :]
    cos, sin = rope_cos_sin(pos, hd, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # context parallelism: shard the q-sequence dim over the model axis —
    # head-count-agnostic (works for 14/40-head archs on a 16-wide axis);
    # K/V stay replicated within the batch shard.
    q = rt.shard(q, "batch", "attn_seq")
    if window and window < S:
        o = local_block_attention(q, k, v, window, rt=rt)
    elif rt.use_pallas:
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=causal)
    else:
        o = blocked_attention(q, k, v, causal=causal,
                              kv_block=rt.attn_kv_block)
    o = rt.shard(o, "batch", "attn_seq")
    return gqa_out(p, o, rt)


def gqa_attention_decode(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
                         pos: jax.Array, *, n_heads: int, n_kv: int, hd: int,
                         rope_theta: float, rt: Runtime,
                         window: int = 0) -> Tuple[jax.Array, Dict]:
    """One-token decode with a statically-sized KV cache.

    cache = {"k": [B, S_max, KV, hd], "v": ...}; `pos` scalar int32 —
    position at which the new token is written.  For window attention the
    cache is ring-buffered at `window` size.
    """
    B, one, _ = x.shape
    q, k_new, v_new = gqa_project(p, x, n_heads, n_kv, hd, rt)
    cos, sin = rope_cos_sin(jnp.full((1, 1), pos), hd, rope_theta)
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)
    S_max = cache["k"].shape[1]
    slot = pos % S_max if window else pos
    k = kv_cache_write(cache["k"], k_new, slot, rt)
    v = kv_cache_write(cache["v"], v_new, slot, rt)
    k = rt.shard(k, "batch", "kv_seq")
    v = rt.shard(v, "batch", "kv_seq")

    G = n_heads // n_kv
    qg = (q * (1.0 / math.sqrt(hd))).reshape(B, 1, n_kv, G, hd)
    s = _gqa_scores(qg, k)                                # [B,KV,G,1,S]
    kv_pos = jnp.arange(S_max)
    if window:
        # ring buffer: slot idx holds absolute position base+idx (idx <= cur)
        # or base-S_max+idx (idx > cur); valid iff 0 <= abs_pos <= pos
        cur = pos % S_max
        base = pos - cur
        abs_pos = jnp.where(kv_pos <= cur, base + kv_pos,
                            base - S_max + kv_pos)
        valid = (abs_pos >= 0) & (abs_pos <= pos)
    else:
        valid = kv_pos <= pos
    s = jnp.where(valid[None, None, None, None, :], s, -jnp.inf)
    p_attn = jax.nn.softmax(s, axis=-1)
    o = _gqa_values(p_attn, v).reshape(B, 1, n_heads, hd)
    y = gqa_out(p, o.astype(rt.compute_dtype), rt)
    return y, {"k": k, "v": v}


# ============================================================== MLA attention

def mla_specs(d: int, n_heads: int, kv_lora: int, nope: int, rope_d: int,
              v_hd: int) -> Dict[str, Spec]:
    return {
        "wq": Spec((d, n_heads * (nope + rope_d)), ("embed", "qkv_fused")),
        "wdkv": Spec((d, kv_lora + rope_d), ("embed", None)),
        "wukv": Spec((kv_lora, n_heads * (nope + v_hd)),
                     (None, "qkv_fused")),
        "wo": Spec((n_heads * v_hd, d), ("qkv_fused", "embed")),
        "kv_norm": Spec((kv_lora,), (None,), "ones"),
    }


def mla_attention_train(p: Params, x: jax.Array, *, n_heads: int,
                        kv_lora: int, nope: int, rope_d: int, v_hd: int,
                        rope_theta: float, eps: float, rt: Runtime,
                        rope: bool = True) -> jax.Array:
    """Multi-head latent attention, expanded (training) form.  With
    `rope` False the decoupled `rope_d` slice is kept but not rotated."""
    cd = rt.compute_dtype
    B, S, _ = x.shape
    q = jnp.einsum("bsd,df->bsf", x, p["wq"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    q = rt.shard(q, "batch", None, "qkv_fused")
    q = q.reshape(B, S, n_heads, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    ckv = jnp.einsum("bsd,df->bsf", x, p["wdkv"].astype(cd),
                     preferred_element_type=jnp.float32)
    c_kv, k_rope = ckv[..., :kv_lora], ckv[..., kv_lora:]
    c_kv = rms_norm(c_kv.astype(cd), p["kv_norm"], eps)
    kv = jnp.einsum("bsl,lf->bsf", c_kv, p["wukv"].astype(cd),
                    preferred_element_type=jnp.float32).astype(cd)
    kv = rt.shard(kv, "batch", None, "qkv_fused")
    kv = kv.reshape(B, S, n_heads, nope + v_hd)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    k_rope = k_rope.astype(cd)[:, :, None, :]
    if rope:
        cos, sin = rope_cos_sin(jnp.arange(S)[None, :], rope_d, rope_theta)
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(k_rope, cos, sin)
    k_rope_b = jnp.broadcast_to(k_rope, (B, S, n_heads, rope_d))

    qf = jnp.concatenate([q_nope, q_rope], -1)
    kf = jnp.concatenate([k_nope, k_rope_b], -1)
    qf = rt.shard(qf, "batch", "attn_seq")
    # scale uses the full qk head dim as in DeepSeek-V2
    o = blocked_attention(qf, kf, v, causal=True, kv_block=rt.attn_kv_block)
    o = rt.shard(o, "batch", "attn_seq")
    y = jnp.einsum("bsf,fd->bsd", o.reshape(B, S, n_heads * v_hd),
                   p["wo"].astype(cd), preferred_element_type=jnp.float32)
    return rt.shard(y.astype(cd), "batch", None, "act_embed")


def mla_attention_decode(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
                         pos: jax.Array, *, n_heads: int, kv_lora: int,
                         nope: int, rope_d: int, v_hd: int,
                         rope_theta: float, eps: float, rt: Runtime,
                         rope: bool = True) -> Tuple[jax.Array, Dict]:
    """Weight-absorbed MLA decode: the cache stores only the compressed
    latent (kv_lora + rope_d per token) — MLA's production memory win."""
    cd = rt.compute_dtype
    B = x.shape[0]
    q = jnp.einsum("bsd,df->bsf", x, p["wq"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    q = q.reshape(B, 1, n_heads, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    if rope:
        cos, sin = rope_cos_sin(jnp.full((1, 1), pos), rope_d, rope_theta)
        q_rope = apply_rope(q_rope, cos, sin)

    ckv = jnp.einsum("bsd,df->bsf", x, p["wdkv"].astype(cd),
                     preferred_element_type=jnp.float32)
    c_new, kr_new = ckv[..., :kv_lora], ckv[..., kv_lora:]
    c_new = rms_norm(c_new.astype(cd), p["kv_norm"], eps)
    kr_new = kr_new.astype(cd)
    if rope:
        kr_new = apply_rope(kr_new[:, :, None, :], cos, sin)[:, :, 0]

    c_cache = kv_cache_write(cache["ckv"], c_new, pos, rt)
    r_cache = kv_cache_write(cache["krope"], kr_new, pos, rt)
    c_cache = rt.shard(c_cache, "batch", "kv_seq")
    r_cache = rt.shard(r_cache, "batch", "kv_seq")

    # absorb W_uk into q:  q_lat[h] = q_nope[h] @ W_uk[h]^T  (lora-dim query)
    wukv = p["wukv"].astype(cd).reshape(kv_lora, n_heads, nope + v_hd)
    w_uk = wukv[..., :nope]                      # [lora, H, nope]
    w_uv = wukv[..., nope:]                      # [lora, H, v_hd]
    q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, w_uk,
                       preferred_element_type=jnp.float32)

    scale = 1.0 / math.sqrt(nope + rope_d)
    s = (jnp.einsum("bqhl,bsl->bhqs", q_lat.astype(cd), c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhr,bsr->bhqs", q_rope, r_cache,
                      preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(c_cache.shape[1]) <= pos
    s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqs,bsl->bqhl", pr.astype(cd), c_cache,
                       preferred_element_type=jnp.float32)   # [B,1,H,lora]
    o = jnp.einsum("bqhl,lhv->bqhv", o_lat.astype(cd), w_uv,
                   preferred_element_type=jnp.float32)        # [B,1,H,v_hd]
    y = jnp.einsum("bqf,fd->bqd",
                   o.astype(cd).reshape(B, 1, n_heads * v_hd),
                   p["wo"].astype(cd), preferred_element_type=jnp.float32)
    return (rt.shard(y.astype(cd), "batch", None, "act_embed"),
            {"ckv": c_cache, "krope": r_cache})


# ===================================================================== MLPs

def swiglu_specs(d: int, f: int) -> Dict[str, Spec]:
    return {
        "w1": Spec((d, f), ("embed", "ff")),
        "w3": Spec((d, f), ("embed", "ff")),
        "w2": Spec((f, d), ("ff", "embed")),
    }


def swiglu(p: Params, x: jax.Array, rt: Runtime) -> jax.Array:
    cd = rt.compute_dtype
    g = jnp.einsum("bsd,df->bsf", x, p["w1"].astype(cd),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("bsd,df->bsf", x, p["w3"].astype(cd),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(cd)
    h = rt.shard(h, "batch", None, "ff")
    y = jnp.einsum("bsf,fd->bsd", h, p["w2"].astype(cd),
                   preferred_element_type=jnp.float32)
    return rt.shard(y.astype(cd), "batch", None, "act_embed")


def gelu_mlp_specs(d: int, f: int) -> Dict[str, Spec]:
    return {
        "w1": Spec((d, f), ("embed", "ff")),
        "b1": Spec((f,), ("ff",), "zeros"),
        "w2": Spec((f, d), ("ff", "embed")),
        "b2": Spec((d,), ("embed",), "zeros"),
    }


def gelu_mlp(p: Params, x: jax.Array, rt: Runtime) -> jax.Array:
    cd = rt.compute_dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w1"].astype(cd),
                   preferred_element_type=jnp.float32) + \
        p["b1"].astype(jnp.float32)
    h = jax.nn.gelu(h).astype(cd)
    h = rt.shard(h, "batch", None, "ff")
    y = jnp.einsum("bsf,fd->bsd", h, p["w2"].astype(cd),
                   preferred_element_type=jnp.float32) + \
        p["b2"].astype(jnp.float32)
    return rt.shard(y.astype(cd), "batch", None, "act_embed")


# ====================================================================== MoE

def moe_specs(d: int, n_experts: int, d_expert: int,
              n_shared: int) -> Dict[str, Spec]:
    s: Dict[str, Spec] = {
        "router": Spec((d, n_experts), ("embed", None)),
        "we1": Spec((n_experts, d, d_expert), ("experts", "embed", None)),
        "we3": Spec((n_experts, d, d_expert), ("experts", "embed", None)),
        "we2": Spec((n_experts, d_expert, d), ("experts", None, "embed")),
    }
    if n_shared:
        s["shared"] = swiglu_specs(d, d_expert * n_shared)
    return s


def _route_experts(p: Params, xf: jax.Array, lo, *, top_k: int,
                   normalize_gates: bool, cd, scoring: str = "softmax",
                   scale: float = 1.0) -> jax.Array:
    """Dropless routed experts of tokens xf [T, D], where the weights in
    `p` hold experts [lo, lo + E_local) of the router's; slots routed to
    other experts add nothing.  Scores are the router's softmax or its
    sigmoid; the top-k gates are renormalised when `normalize_gates`, then
    multiplied by `scale` (routed_scaling_factor).  The slots held here
    are sorted by expert and the three expert GEMMs run as
    `jax.lax.ragged_dot` with the group sizes the routing gives, so no
    expert has a capacity and no buffer is padded."""
    T, D = xf.shape
    n_local = p["we1"].shape[0]
    logits = jnp.einsum("td,de->te", xf, p["router"].astype(cd),
                        preferred_element_type=jnp.float32)
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, top_k)          # [T, k]
    if normalize_gates:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        gate = gate * scale

    e_flat = eidx.reshape(-1) - lo                    # [T*k] local expert
    held = (e_flat >= 0) & (e_flat < n_local)
    e_flat = jnp.where(held, e_flat, n_local)         # others sort last
    order = jnp.argsort(e_flat, stable=True)          # grouped by expert
    sizes = jnp.bincount(e_flat, length=n_local + 1)[:n_local]
    # a token's top-k experts are distinct, so at most min(k, E_local) of
    # its slots are held here: the sorted rows past that bound are unheld
    rows = T * min(top_k, n_local)
    xs = xf[order[:rows] // top_k]                    # [rows, D] dispatch

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w.astype(cd), sizes.astype(jnp.int32),
                                  preferred_element_type=jnp.float32)

    h = (jax.nn.silu(grouped(xs, p["we1"]))
         * grouped(xs, p["we3"])).astype(cd)
    ys = grouped(h, p["we2"]).astype(cd)              # [rows, D]

    # combine: undo the sort, weight each held slot by its gate
    y_rep = ys[jnp.minimum(jnp.argsort(order), rows - 1)]
    y_rep = jnp.where(held[:, None], y_rep, jnp.zeros((), cd))
    y_rep = y_rep.reshape(T, top_k, D)
    return (y_rep * gate[..., None].astype(cd)).sum(axis=1)


def moe_block(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
              normalize_gates: bool, rt: Runtime, scoring: str = "softmax",
              scale: float = 1.0) -> jax.Array:
    """Token-choice top-k MoE, dropless (every routed token is computed).

    On a mesh the block is a `shard_map`: each device routes the tokens of
    its batch shard to the experts of its "experts" shard, and a psum over
    the experts axis combines them, so dispatch stays local to the batch
    shard and the expert weights stay sharded.  Dispatch and combine are
    a gather and its inverse permutation: *memory* traffic, not FLOPs.
    """
    cd = rt.compute_dtype
    B, S, D = x.shape
    assert p["router"].shape[1] == n_experts, (p["router"].shape, n_experts)
    routed = {k: p[k] for k in ("router", "we1", "we3", "we2")}
    route = functools.partial(_route_experts, top_k=top_k,
                              normalize_gates=normalize_gates, cd=cd,
                              scoring=scoring, scale=scale)
    if rt.mesh is None or rt.rules is None:
        y = route(routed, x.reshape(B * S, D), 0).reshape(B, S, D)
    else:
        P = jax.sharding.PartitionSpec
        batch = rt.rules.get("batch")
        ex = rt.rules.get("experts")

        def local(q, xl):
            lo = (jax.lax.axis_index(ex) * q["we1"].shape[0]) if ex else 0
            yl = route(q, xl.reshape(-1, D), lo).reshape(xl.shape)
            return jax.lax.psum(yl, ex) if ex else yl

        w_spec = P(ex, None, None)
        y = jax.shard_map(
            local, mesh=rt.mesh,
            in_specs=({"router": P(), "we1": w_spec, "we3": w_spec,
                       "we2": w_spec}, P(batch)),
            out_specs=P(batch))(routed, x)

    if "shared" in p:
        y = y + swiglu(p["shared"], x, rt)
    return rt.shard(y, "batch", None, "act_embed")


# ================================================================== RG-LRU

def rglru_specs(d: int, w: int, n_heads: int, conv_w: int) -> Dict[str, Spec]:
    hd = w // n_heads
    return {
        "wx": Spec((d, w), ("embed", "lru")),
        "wy": Spec((d, w), ("embed", "lru")),          # gelu gate branch
        "conv_w": Spec((conv_w, w), (None, "lru"), "small"),
        "conv_b": Spec((w,), ("lru",), "zeros"),
        # block-diagonal (per-head) recurrence & input gates
        "wa": Spec((n_heads, hd, hd), (None, None, None), "small"),
        "ba": Spec((w,), ("lru",), "zeros"),
        "wi": Spec((n_heads, hd, hd), (None, None, None), "small"),
        "bi": Spec((w,), ("lru",), "zeros"),
        "a_param": Spec((w,), ("lru",), "rglru_a"),
        "wout": Spec((w, d), ("lru", "embed")),
    }


_RGLRU_C = 8.0


def _rglru_gates(p: Params, xb: jax.Array, n_heads: int) -> Tuple[jax.Array,
                                                                  jax.Array]:
    """Block-diagonal gate projections; xb [B, S, W] fp32."""
    B, S, W = xb.shape
    hd = W // n_heads
    xh = xb.reshape(B, S, n_heads, hd)
    ra = jnp.einsum("bshi,hij->bshj", xh, p["wa"].astype(jnp.float32))
    ri = jnp.einsum("bshi,hij->bshj", xh, p["wi"].astype(jnp.float32))
    r = jax.nn.sigmoid(ra.reshape(B, S, W) + p["ba"].astype(jnp.float32))
    i = jax.nn.sigmoid(ri.reshape(B, S, W) + p["bi"].astype(jnp.float32))
    return r, i


def _causal_conv1d(x: jax.Array, w: jax.Array,
                   b: Optional[jax.Array] = None,
                   prefix: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv over seq; x [B,S,W], w [K,W]: a grouped
    `conv_general_dilated` (one group per channel, costed as Table 1's
    depthwise row), left-padded with `prefix` [B,K-1,W], the decode-time
    history, or zeros."""
    K, W = w.shape
    if prefix is None:
        prefix = jnp.zeros((x.shape[0], K - 1, W), x.dtype)
    xp = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
    out = jax.lax.conv_general_dilated(
        xp, w.astype(x.dtype)[:, None, :], window_strides=(1,),
        padding="VALID", dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=W)
    return out if b is None else out + b.astype(x.dtype)


def rglru_block_train(p: Params, x: jax.Array, *, n_heads: int,
                      rt: Runtime) -> jax.Array:
    """Griffin recurrent block: conv1d -> RG-LRU, gated by a GeLU branch."""
    cd = rt.compute_dtype
    xb = jnp.einsum("bsd,dw->bsw", x, p["wx"].astype(cd),
                    preferred_element_type=jnp.float32)
    gate = jnp.einsum("bsd,dw->bsw", x, p["wy"].astype(cd),
                      preferred_element_type=jnp.float32)
    xb = rt.shard(xb.astype(jnp.float32), "batch", None, "lru")
    xb = _causal_conv1d(xb, p["conv_w"], p["conv_b"])

    r, i = _rglru_gates(p, xb, n_heads)
    log_a0 = -_RGLRU_C * jax.nn.softplus(p["a_param"].astype(jnp.float32))
    log_a = log_a0[None, None, :] * r                     # [B,S,W]
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    b_t = beta * (i * xb)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    a_s, h = jax.lax.associative_scan(combine, (a, b_t), axis=1)
    y = h * jax.nn.gelu(gate)
    y = jnp.einsum("bsw,wd->bsd", y.astype(cd), p["wout"].astype(cd),
                   preferred_element_type=jnp.float32)
    return rt.shard(y.astype(cd), "batch", None, "act_embed")


def rglru_block_decode(p: Params, x: jax.Array, state: Dict[str, jax.Array],
                       *, n_heads: int, rt: Runtime
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """state = {"h": [B, W] fp32, "conv": [B, K-1, W] fp32}."""
    cd = rt.compute_dtype
    xb = jnp.einsum("bsd,dw->bsw", x, p["wx"].astype(cd),
                    preferred_element_type=jnp.float32)
    gate = jnp.einsum("bsd,dw->bsw", x, p["wy"].astype(cd),
                      preferred_element_type=jnp.float32)
    xb = xb.astype(jnp.float32)
    conv_hist = jnp.concatenate([state["conv"], xb], axis=1)
    xc = _causal_conv1d(xb, p["conv_w"], p["conv_b"], prefix=state["conv"])
    r, i = _rglru_gates(p, xc, n_heads)
    log_a0 = -_RGLRU_C * jax.nn.softplus(p["a_param"].astype(jnp.float32))
    log_a = log_a0[None, None, :] * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    h = a[:, 0] * state["h"] + (beta * (i * xc))[:, 0]
    y = h[:, None, :] * jax.nn.gelu(gate)
    y = jnp.einsum("bsw,wd->bsd", y.astype(cd), p["wout"].astype(cd),
                   preferred_element_type=jnp.float32)
    new_state = {"h": h, "conv": conv_hist[:, 1:]}
    return rt.shard(y.astype(cd), "batch", None, "act_embed"), new_state


# ===================================================================== KDA

def kda_specs(d: int, n_heads: int, hd: int, conv: int,
              rank: int) -> Dict[str, Spec]:
    """Kimi Delta Attention (arXiv:2510.26692; fla-org `KimiDeltaAttention`):
    fused q/k/v projection with its short conv, a low-rank per-channel
    decay, a per-head write strength beta, and a low-rank sigmoid output
    gate over a per-head RMSNorm."""
    w = n_heads * hd
    return {
        "wqkv": Spec((d, 3 * w), ("embed", "qkv_fused")),
        "conv_w": Spec((conv, 3 * w), (None, "qkv_fused"), "conv"),
        "wf_down": Spec((d, rank), ("embed", None)),
        "wf_up": Spec((rank, w), (None, "qkv_fused")),
        "dt_bias": Spec((w,), ("qkv_fused",), "zeros"),
        "a_log": Spec((n_heads,), (None,), "kda_a_log"),
        "wb": Spec((d, n_heads), ("embed", None)),
        "wg_down": Spec((d, rank), ("embed", None)),
        "wg_up": Spec((rank, w), (None, "qkv_fused")),
        "g_bias": Spec((w,), ("qkv_fused",), "zeros"),
        "o_norm": Spec((hd,), (None,), "ones"),
        "wo": Spec((w, d), ("qkv_fused", "embed")),
    }


def _l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + eps)


def _kda_inputs(p: Params, x: jax.Array, prefix: Optional[jax.Array],
                n_heads: int, hd: int, rt: Runtime):
    """q, k, v [B,S,H,hd] (SiLU of the short conv; q and k L2-normalised
    per head, q scaled by hd^-1/2), the log-decay g [B,S,H,hd] <= 0, beta
    [B,S,H], and the conv's input (the decode cache keeps its tail)."""
    cd = rt.compute_dtype
    B, S, _ = x.shape
    f32 = jnp.float32
    qkv = jnp.einsum("bsd,df->bsf", x, p["wqkv"].astype(cd),
                     preferred_element_type=f32)
    act = jax.nn.silu(_causal_conv1d(qkv, p["conv_w"], prefix=prefix))
    q, k, v = (t.reshape(B, S, n_heads, hd)
               for t in jnp.split(act, 3, axis=-1))
    q = _l2norm(q) * (1.0 / math.sqrt(hd))
    k = _l2norm(k)
    f = jnp.einsum("bsr,rf->bsf",
                   jnp.einsum("bsd,dr->bsr", x, p["wf_down"].astype(cd),
                              preferred_element_type=f32).astype(cd),
                   p["wf_up"].astype(cd), preferred_element_type=f32)
    f = (f + p["dt_bias"].astype(f32)).reshape(B, S, n_heads, hd)
    g = -jnp.exp(p["a_log"].astype(f32))[:, None] * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, p["wb"].astype(cd),
                                     preferred_element_type=f32))
    return q, k, v, g, beta, qkv


def _kda_out(p: Params, o: jax.Array, x: jax.Array, eps: float,
             rt: Runtime) -> jax.Array:
    """y = W_o [RMSNorm_head(o) * sigmoid(W_g_up W_g_down x + b)]."""
    cd = rt.compute_dtype
    B, S, H, hd = o.shape
    f32 = jnp.float32
    gate = jnp.einsum("bsr,rf->bsf",
                      jnp.einsum("bsd,dr->bsr", x, p["wg_down"].astype(cd),
                                 preferred_element_type=f32).astype(cd),
                      p["wg_up"].astype(cd), preferred_element_type=f32)
    gate = jax.nn.sigmoid(gate + p["g_bias"].astype(f32))
    o = rms_norm(o.astype(f32), p["o_norm"], eps).reshape(B, S, H * hd)
    y = jnp.einsum("bsf,fd->bsd", (o * gate).astype(cd), p["wo"].astype(cd),
                   preferred_element_type=f32)
    return rt.shard(y.astype(cd), "batch", None, "act_embed")


KDA_CHUNK = 64          # tokens per chunk of the prefill form (fla's too)


def _kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                 beta: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Chunked (WY) form of the KDA recurrence

        S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T,
        o_t = S_t^T q_t

    over q, k, g [B,S,H,dk], v [B,S,H,dv], beta [B,S,H] from a zero
    state; returns o [B,S,H,dv] and the last state [B,H,dk,dv].

    Per chunk of C = KDA_CHUNK tokens, with G the within-chunk cumulative
    log-decay (G <= 0, decreasing), S_i = e^G_i * S_0 + sum_{j<=i}
    (e^{G_i-G_j} k_j) w_j^T, where the pseudo-values w solve
    (I + diag(b) A) w = b v - b (e^G k) S_0 and A_ij = sum_c k_ic k_jc e^{G_ic-G_jc} (j < i): w =
    T b v - T b (e^G k) S_0 with T the unit-lower-triangular inverse.
    No exponent is positive (with g down to -100 per token, e^{-G}
    overflows float32 within a chunk): A and the output scores P (same
    form with q_i, j <= i) are built from 16-token sub-chunks, each pair
    within a sub-chunk decayed by e^{G_i-G_j}, i >= j, and each earlier
    key decayed to the position before the query's sub-chunk, the query
    from there.  Every step whose work grows as C*d or C^2*d is an einsum;
    only the C x C triangular inverse (C^3/6 per chunk and head) is not.
    Padded tail positions have k = v = g = b = 0: no write, no decay."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    C, sub = KDA_CHUNK, 16
    ns = C // sub
    nc = -(-S // C)
    pad = nc * C - S
    if pad:
        def padded(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = map(padded, (q, k, v, g, beta))

    def chunks(t):              # [B, nc*C, H, ...] -> [nc, B, H, C, ...]
        t = t.astype(f32).reshape((B, nc, C) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 3, 2)

    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    same_block = (jnp.arange(ns)[:, None, None, None]
                  == jnp.arange(ns)[None, None, :, None])   # [ns,1,ns,1]
    before = (jnp.arange(C)[None, :] < (jnp.arange(ns) * sub)[:, None])

    def step(S0, blk):
        qc, kc, vc, gc, bc = blk          # [B,H,C,d]; bc [B,H,C,1]
        G = jnp.cumsum(gc, axis=2)
        Gs = G.reshape(B, H, ns, sub, dk)
        ks = kc.reshape(B, H, ns, sub, dk)
        qs = qc.reshape(B, H, ns, sub, dk)
        # pairs inside a sub-chunk: e^{G_i - G_j}, i >= j (i < j reads
        # e^0 here and is masked out of the C x C matrices below)
        rel = jnp.minimum(Gs[:, :, :, :, None, :] - Gs[:, :, :, None, :, :],
                          0.0)
        k_rel = ks[:, :, :, None, :, :] * jnp.exp(rel)    # [B,H,ns,i,j,dk]
        a_in = jnp.einsum("bhnic,bhnijc->bhnij", ks, k_rel)
        p_in = jnp.einsum("bhnic,bhnijc->bhnij", qs, k_rel)
        # earlier sub-chunks through the position before the query's own
        # (later keys read e^0 and are replaced by the pairs above)
        ref = jnp.concatenate([jnp.zeros_like(Gs[:, :, :1, 0]),
                               Gs[:, :, :-1, -1]], axis=2)  # [B,H,ns,dk]
        to_ref = jnp.exp(Gs - ref[:, :, :, None, :])
        k_dec = kc[:, :, None] * jnp.exp(jnp.minimum(
            ref[:, :, :, None, :] - G[:, :, None, :, :], 0.0))
        a_out = jnp.einsum("bhnic,bhnjc->bhnij", ks * to_ref, k_dec)
        p_out = jnp.einsum("bhnic,bhnjc->bhnij", qs * to_ref, k_dec)

        def whole(inner, outer, mask):    # -> [B,H,C,C]
            diag = (inner[:, :, :, :, None, :] * same_block).reshape(
                B, H, ns, sub, C)
            full = jnp.where(before[:, None, :], outer, diag)
            return jnp.where(mask, full.reshape(B, H, C, C), 0.0)

        a = bc * whole(a_in, a_out, strict)
        p = whole(p_in, p_out, lower)
        eye = jnp.eye(C, dtype=f32)
        t = jax.lax.linalg.triangular_solve(
            eye + a, jnp.broadcast_to(eye, a.shape), left_side=True,
            lower=True, unit_diagonal=True)
        decay = jnp.exp(G)
        u = jnp.einsum("bhij,bhjv->bhiv", t, bc * vc)
        w_k = jnp.einsum("bhij,bhjc->bhic", t, bc * decay * kc)
        w = u - jnp.einsum("bhic,bhcv->bhiv", w_k, S0)
        o = (jnp.einsum("bhic,bhcv->bhiv", decay * qc, S0)
             + jnp.einsum("bhij,bhjv->bhiv", p, w))
        k_end = kc * jnp.exp(G[:, :, -1:] - G)
        S1 = (jnp.exp(G[:, :, -1])[..., None] * S0
              + jnp.einsum("bhic,bhiv->bhcv", k_end, w))
        return S1, o

    last, o = jax.lax.scan(jax.checkpoint(step),
                           jnp.zeros((B, H, dk, dv), f32),
                           tuple(map(chunks, (q, k, v, g, beta[..., None]))))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)     # [B, nc, C, H, dv]
    return o.reshape(B, nc * C, H, dv)[:, :S], last


def kda_block_train(p: Params, x: jax.Array, *, n_heads: int, hd: int,
                    eps: float, rt: Runtime) -> jax.Array:
    """KDA over a whole sequence in the chunked form."""
    q, k, v, g, beta, _ = _kda_inputs(p, x, None, n_heads, hd, rt)
    o, _ = _kda_chunked(q, k, v, g, beta)
    return _kda_out(p, o, x, eps, rt)


def kda_block_decode(p: Params, x: jax.Array, state: Dict[str, jax.Array],
                     *, n_heads: int, hd: int, eps: float, rt: Runtime
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One token through the recurrence; state = {"S": [B,H,dk,dv],
    "conv": [B,K-1,3*H*hd]} fp32.  The rank-1 write k (x) u is an einsum,
    dk*dv MACs per head; the decay is elementwise."""
    q, k, v, g, beta, qkv = _kda_inputs(p, x, state["conv"], n_heads, hd,
                                        rt)
    q, k, v, g, beta = q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
    S = state["S"] * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, S))
    S = S + jnp.einsum("bhk,bhv->bhkv", k, u)
    o = jnp.einsum("bhk,bhkv->bhv", q, S)[:, None]
    hist = jnp.concatenate([state["conv"], qkv], axis=1)[:, 1:]
    return _kda_out(p, o, x, eps, rt), {"S": S, "conv": hist}


# =================================================================== mLSTM

def mlstm_specs(d: int, n_heads: int) -> Dict[str, Spec]:
    u = 2 * d                                    # proj_factor = 2
    hd = u // n_heads
    return {
        "w_up": Spec((d, u), ("embed", "ff")),
        "w_gate": Spec((d, u), ("embed", "ff")),
        "wq": Spec((n_heads, hd, hd), (None, None, None), "small"),
        "wk": Spec((n_heads, hd, hd), (None, None, None), "small"),
        "wv": Spec((n_heads, hd, hd), (None, None, None), "small"),
        "w_if": Spec((u, 2 * n_heads), ("ff", None), "small"),
        "b_if": Spec((2 * n_heads,), (None,), "zeros"),
        "w_down": Spec((u, d), ("ff", "embed")),
        "ln_inner": Spec((u,), ("ff",), "ones"),
    }


def _mlstm_chunkwise(q: jax.Array, k: jax.Array, v: jax.Array,
                     log_i: jax.Array, log_f: jax.Array, chunk: int,
                     state: Optional[Tuple] = None,
                     ) -> Tuple[jax.Array, Tuple]:
    """Chunkwise-parallel mLSTM (matrix-memory linear attention with scalar
    per-head exponential input and sigmoid forget gates).

    q,k,v [B,S,H,hd]; log_i/log_f [B,S,H].  Returns y [B,S,H,hd] and final
    (C [B,H,hd,hd], n [B,H,hd], m [B,H]).  fp32 gate math throughout.
    """
    B, S, H, hd = q.shape
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_i = jnp.pad(log_i, ((0, 0), (0, pad), (0, 0)),
                        constant_values=-1e9)
        log_f = jnp.pad(log_f, ((0, 0), (0, pad), (0, 0)))
    L = chunk

    def resh(x):
        return x.reshape(B, nc, L, *x.shape[2:]).swapaxes(0, 1)

    qc, kc, vc = resh(q), resh(k), resh(v)
    lic, lfc = resh(log_i), resh(log_f)
    scale = 1.0 / math.sqrt(hd)

    if state is None:
        C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        n0 = jnp.zeros((B, H, hd), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state

    def step(carry, blk):
        C, n, m = carry
        qb, kb, vb, li, lf = blk                   # [B,L,H,*]
        csum = jnp.cumsum(lf, axis=1)              # inclusive cum log f
        total = csum[:, -1]                        # [B,H]
        # decay from j to i (i >= j): csum_i - csum_j + li_j
        dec = (csum[:, :, None, :] - csum[:, None, :, :]
               + li[:, None, :, :])                # [B,Li,Lj,H]
        causal = jnp.tril(jnp.ones((L, L), bool))
        dec = jnp.where(causal[None, :, :, None], dec, -jnp.inf)
        m_intra = dec.max(axis=2)                  # [B,Li,H]
        m_inter = csum + m[:, None, :]             # [B,Li,H]
        m_new_t = jnp.maximum(m_intra, m_inter)    # running per-step max
        d_intra = jnp.exp(dec - m_new_t[:, :, None, :])
        d_inter = jnp.exp(m_inter - m_new_t)

        s = jnp.einsum("blhd,bmhd->blmh", qb.astype(jnp.float32) * scale,
                       kb.astype(jnp.float32))
        sd = s * d_intra
        y_intra = jnp.einsum("blmh,bmhd->blhd", sd, vb.astype(jnp.float32))
        y_inter = jnp.einsum("blhd,bhde->blhe",
                             qb.astype(jnp.float32) * scale
                             * d_inter[..., None], C)
        # normalizer state: n_l = sum_j D_lj k_j (decay only — q enters once
        # via the dot product below)
        n_intra = jnp.einsum("blmh,bmhd->blhd", d_intra,
                             kb.astype(jnp.float32))
        n_inter = n[:, None] * d_inter[..., None]
        num = y_intra + y_inter
        den = jnp.abs(jnp.einsum(
            "blhd,blhd->blh", qb.astype(jnp.float32) * scale,
            n_intra + n_inter))
        y = num / jnp.maximum(den, jnp.exp(-m_new_t))[..., None]

        # carry update (decay each key's contribution to chunk end)
        m_end = jnp.maximum(total + m, (total[:, None] - csum + li
                                        ).max(axis=1))
        w_key = jnp.exp(total[:, None] - csum + li - m_end[:, None])
        C_new = C * jnp.exp(total + m - m_end)[..., None, None] + \
            jnp.einsum("blh,blhd,blhe->bhde", w_key,
                       kb.astype(jnp.float32), vb.astype(jnp.float32))
        n_new = n * jnp.exp(total + m - m_end)[..., None] + \
            jnp.einsum("blh,blhd->bhd", w_key, kb.astype(jnp.float32))
        return (C_new, n_new, m_end), y

    (C, n, m), ys = jax.lax.scan(jax.checkpoint(step), (C0, n0, m0),
                                 (qc, kc, vc, lic, lfc))
    y = ys.swapaxes(0, 1).reshape(B, nc * L, H, hd)[:, :S]
    return y, (C, n, m)


def mlstm_block_train(p: Params, x: jax.Array, *, n_heads: int, eps: float,
                      rt: Runtime) -> jax.Array:
    cd = rt.compute_dtype
    B, S, D = x.shape
    u = p["w_up"].shape[1]
    hd = u // n_heads
    xb = jnp.einsum("bsd,du->bsu", x, p["w_up"].astype(cd),
                    preferred_element_type=jnp.float32).astype(cd)
    z = jnp.einsum("bsd,du->bsu", x, p["w_gate"].astype(cd),
                   preferred_element_type=jnp.float32)
    xb = rt.shard(xb, "batch", None, "ff")
    xh = xb.reshape(B, S, n_heads, hd)
    q = jnp.einsum("bshi,hij->bshj", xh, p["wq"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    k = jnp.einsum("bshi,hij->bshj", xh, p["wk"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    v = jnp.einsum("bshi,hij->bshj", xh, p["wv"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    gates = jnp.einsum("bsu,ug->bsg", xb, p["w_if"].astype(cd),
                       preferred_element_type=jnp.float32) + \
        p["b_if"].astype(jnp.float32)
    log_i, f_pre = gates[..., :n_heads], gates[..., n_heads:]
    log_f = jax.nn.log_sigmoid(f_pre)
    y, _ = _mlstm_chunkwise(q, k, v, log_i, log_f, rt.mlstm_chunk)
    y = rms_norm(y.reshape(B, S, u).astype(cd), p["ln_inner"], eps)
    y = y * jax.nn.silu(z).astype(cd)
    out = jnp.einsum("bsu,ud->bsd", y, p["w_down"].astype(cd),
                     preferred_element_type=jnp.float32)
    return rt.shard(out.astype(cd), "batch", None, "act_embed")


def mlstm_block_decode(p: Params, x: jax.Array, state: Dict[str, jax.Array],
                       *, n_heads: int, eps: float, rt: Runtime
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """state = {"C": [B,H,hd,hd], "n": [B,H,hd], "m": [B,H]} fp32."""
    cd = rt.compute_dtype
    B, one, D = x.shape
    u = p["w_up"].shape[1]
    hd = u // n_heads
    xb = jnp.einsum("bsd,du->bsu", x, p["w_up"].astype(cd),
                    preferred_element_type=jnp.float32).astype(cd)
    z = jnp.einsum("bsd,du->bsu", x, p["w_gate"].astype(cd),
                   preferred_element_type=jnp.float32)
    xh = xb.reshape(B, n_heads, hd)
    q = jnp.einsum("bhi,hij->bhj", xh, p["wq"].astype(cd)).astype(jnp.float32)
    k = jnp.einsum("bhi,hij->bhj", xh, p["wk"].astype(cd)).astype(jnp.float32)
    v = jnp.einsum("bhi,hij->bhj", xh, p["wv"].astype(cd)).astype(jnp.float32)
    gates = jnp.einsum("bu,ug->bg", xb[:, 0], p["w_if"].astype(cd),
                       preferred_element_type=jnp.float32) + \
        p["b_if"].astype(jnp.float32)
    log_i, f_pre = gates[..., :n_heads], gates[..., n_heads:]
    log_f = jax.nn.log_sigmoid(f_pre)

    C, n, m = state["C"], state["n"], state["m"]
    m_new = jnp.maximum(log_f + m, log_i)
    w_f = jnp.exp(log_f + m - m_new)
    w_i = jnp.exp(log_i - m_new)
    C_new = C * w_f[..., None, None] + \
        w_i[..., None, None] * k[..., :, None] * v[..., None, :]
    n_new = n * w_f[..., None] + w_i[..., None] * k
    scale = 1.0 / math.sqrt(hd)
    num = jnp.einsum("bhd,bhde->bhe", q * scale, C_new)
    den = jnp.abs(jnp.einsum("bhd,bhd->bh", q * scale, n_new))
    y = num / jnp.maximum(den, jnp.exp(-m_new))[..., None]
    y = rms_norm(y.reshape(B, 1, u).astype(cd), p["ln_inner"], eps)
    y = y * jax.nn.silu(z).astype(cd)
    out = jnp.einsum("bsu,ud->bsd", y, p["w_down"].astype(cd),
                     preferred_element_type=jnp.float32)
    new_state = {"C": C_new, "n": n_new, "m": m_new}
    return rt.shard(out.astype(cd), "batch", None, "act_embed"), new_state


# =================================================================== sLSTM

def slstm_specs(d: int, n_heads: int) -> Dict[str, Spec]:
    hd = d // n_heads
    return {
        "w_in": Spec((d, 4 * d), ("embed", "ff")),       # z,i,f,o pre-acts
        "b_in": Spec((4 * d,), ("ff",), "zeros"),
        "r": Spec((4, n_heads, hd, hd), (None, None, None, None), "small"),
        "ln_inner": Spec((d,), ("embed",), "ones"),
    }


def _slstm_cell(wx: jax.Array, h_prev: jax.Array, state: Tuple,
                r: jax.Array, n_heads: int) -> Tuple[jax.Array, Tuple]:
    """One sLSTM step.  wx [B, 4D] input pre-activations (fp32);
    state = (c, n, m) each [B, D]."""
    c, n, m = state
    B, D4 = wx.shape
    D = D4 // 4
    hd = D // n_heads
    hh = h_prev.reshape(B, n_heads, hd)
    rec = jnp.einsum("bhi,ghij->bghj", hh, r.astype(jnp.float32))
    rec = rec.reshape(B, 4 * D)
    pre = wx + rec
    z_pre, i_pre, f_pre, o_pre = jnp.split(pre, 4, axis=-1)
    z = jnp.tanh(z_pre)
    o = jax.nn.sigmoid(o_pre)
    log_i = i_pre                                   # exponential input gate
    log_f = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(log_f + m, log_i)
    w_f = jnp.exp(log_f + m - m_new)
    w_i = jnp.exp(log_i - m_new)
    c_new = w_f * c + w_i * z
    n_new = w_f * n + w_i
    h = o * c_new / jnp.maximum(n_new, 1.0)
    return h, (c_new, n_new, m_new)


def slstm_block_train(p: Params, x: jax.Array, *, n_heads: int, eps: float,
                      rt: Runtime) -> jax.Array:
    cd = rt.compute_dtype
    B, S, D = x.shape
    wx = jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(cd),
                    preferred_element_type=jnp.float32) + \
        p["b_in"].astype(jnp.float32)

    def step(carry, wx_t):
        h_prev, st = carry
        h, st = _slstm_cell(wx_t, h_prev, st, p["r"], n_heads)
        return (h, st), h

    init = (jnp.zeros((B, D), jnp.float32),
            (jnp.zeros((B, D), jnp.float32), jnp.zeros((B, D), jnp.float32),
             jnp.full((B, D), -1e30, jnp.float32)))
    (_, _), hs = jax.lax.scan(step, init, wx.swapaxes(0, 1))
    y = hs.swapaxes(0, 1)                                 # [B,S,D]
    y = rms_norm(y.astype(cd), p["ln_inner"], eps)
    return rt.shard(y, "batch", None, "act_embed")


def slstm_block_decode(p: Params, x: jax.Array, state: Dict[str, jax.Array],
                       *, n_heads: int, eps: float, rt: Runtime
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """state = {"h","c","n","m"} each [B, D] fp32."""
    cd = rt.compute_dtype
    B, one, D = x.shape
    wx = jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(cd),
                    preferred_element_type=jnp.float32)[:, 0] + \
        p["b_in"].astype(jnp.float32)
    h, (c, n, m) = _slstm_cell(wx, state["h"],
                               (state["c"], state["n"], state["m"]),
                               p["r"], n_heads)
    y = rms_norm(h[:, None].astype(cd), p["ln_inner"], eps)
    return (rt.shard(y, "batch", None, "act_embed"),
            {"h": h, "c": c, "n": n, "m": m})
