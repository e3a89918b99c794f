"""Decoder language model assembled from `repro.models.layers` blocks.

Layer stacking uses `jax.lax.scan` over *pattern groups*: the repeating
block pattern of the architecture (e.g. recurrentgemma's
(rglru, rglru, local_attn)) is one scan body, with that unit's parameters
stacked along a leading `repeats` axis.  This keeps the lowered HLO small
(one unit traced once) — essential for fast multi-pod compilation — and is
the structure XLA's latency-hiding scheduler pipelines best.

Supports: dense GQA (qwen2*, mistral-nemo), MoE (olmoe), MLA+MoE
(deepseek-v2-lite), RG-LRU hybrid (recurrentgemma), xLSTM (mlstm+slstm),
and VLM stubs (internvl2: patch-embedding prefix).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.models.layers import Runtime, Spec

Params = Any
PyTree = Any

__all__ = ["DecoderLM", "Group"]


@dataclasses.dataclass(frozen=True)
class Group:
    """A scan group: `unit` (tuple of block kinds) repeated `repeats` times."""

    unit: Tuple[str, ...]
    repeats: int


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  rt: Runtime) -> jax.Array:
    """Vocab-sharded cross-entropy [B, S].

    Never gathers the full logits: logsumexp reduces the sharded vocab dim
    (partial reduce + AllReduce under GSPMD) and the label log-prob is a
    one-hot contraction over the same sharded dim — both stay vocab-parallel.
    """
    m = jax.lax.stop_gradient(
        jnp.max(logits, axis=-1, keepdims=True)).astype(jnp.float32)
    ex_sum = jnp.sum(jnp.exp(logits.astype(jnp.float32) - m), axis=-1)
    lse = jnp.log(ex_sum) + m[..., 0]
    oh = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    oh = rt.shard(oh, "batch", None, "vocab")
    ll = jnp.einsum("bsv,bsv->bs", logits, oh,
                    preferred_element_type=jnp.float32)
    return lse - ll


def plan_groups(cfg: ArchConfig) -> List[Group]:
    n = cfg.num_layers
    groups: List[Group] = []
    if cfg.moe is not None and cfg.moe.first_dense:
        groups.append(Group(("attn_dense",) * cfg.moe.first_dense, 1))
        n -= cfg.moe.first_dense
    unit = cfg.block_pattern
    r, rem = divmod(n, len(unit))
    if r:
        groups.append(Group(unit, r))
    if rem:
        groups.append(Group(unit[:rem], 1))
    return groups


# =========================================================== block dispatch

def _slstm_ff_dim(d: int) -> int:
    return -(-int(4 * d / 3) // 128) * 128


def block_specs(cfg: ArchConfig, kind: str) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    s: Dict[str, Any] = {"ln1": Spec((d,), ("embed",), "ones")}
    if kind in ("attn", "attn_dense", "local_attn"):
        if cfg.mla is not None:
            m = cfg.mla
            s["attn"] = L.mla_specs(d, cfg.num_heads, m.kv_lora_rank,
                                    m.qk_nope_head_dim, m.qk_rope_head_dim,
                                    m.v_head_dim)
        else:
            s["attn"] = L.gqa_specs(d, cfg.num_heads, cfg.num_kv_heads, hd,
                                    cfg.qkv_bias)
        s["ln2"] = Spec((d,), ("embed",), "ones")
        if kind == "attn_dense":
            ff = cfg.moe.dense_d_ff if cfg.moe else cfg.d_ff
            s["mlp"] = L.swiglu_specs(d, ff)
        elif cfg.moe is not None:
            s["moe"] = L.moe_specs(d, cfg.moe.num_experts, cfg.moe.d_expert,
                                   cfg.moe.num_shared)
        else:
            s["mlp"] = L.swiglu_specs(d, cfg.d_ff)
    elif kind == "rglru":
        w = cfg.lru_width or d
        s["rglru"] = L.rglru_specs(d, w, cfg.num_heads, cfg.conv1d_width)
        s["ln2"] = Spec((d,), ("embed",), "ones")
        s["mlp"] = L.swiglu_specs(d, cfg.d_ff)
    elif kind == "mlstm":
        s["mlstm"] = L.mlstm_specs(d, cfg.num_heads)
    elif kind == "slstm":
        s["slstm"] = L.slstm_specs(d, cfg.num_heads)
        s["ln2"] = Spec((d,), ("embed",), "ones")
        s["mlp"] = L.swiglu_specs(d, _slstm_ff_dim(d))
    else:
        raise ValueError(kind)
    return s


def block_apply_train(cfg: ArchConfig, kind: str, p: Params, x: jax.Array,
                      rt: Runtime) -> jax.Array:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    eps = cfg.norm_eps
    h = L.rms_norm(x, p["ln1"], eps)
    if kind in ("attn", "attn_dense", "local_attn"):
        window = cfg.local_window if kind == "local_attn" else 0
        if cfg.mla is not None:
            m = cfg.mla
            a = L.mla_attention_train(
                p["attn"], h, n_heads=cfg.num_heads,
                kv_lora=m.kv_lora_rank, nope=m.qk_nope_head_dim,
                rope_d=m.qk_rope_head_dim, v_hd=m.v_head_dim,
                rope_theta=cfg.rope_theta, eps=eps, rt=rt)
        else:
            a = L.gqa_attention_train(
                p["attn"], h, n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
                hd=hd, rope_theta=cfg.rope_theta, rt=rt, causal=True,
                window=window)
        x = x + a
        h2 = L.rms_norm(x, p["ln2"], eps)
        if "moe" in p:
            m = cfg.moe
            y = L.moe_block(p["moe"], h2, n_experts=m.num_experts,
                            top_k=m.top_k,
                            normalize_gates=m.norm_topk_prob, rt=rt)
        else:
            y = L.swiglu(p["mlp"], h2, rt)
        return x + y
    if kind == "rglru":
        a = L.rglru_block_train(p["rglru"], h, n_heads=cfg.num_heads, rt=rt)
        x = x + a
        h2 = L.rms_norm(x, p["ln2"], eps)
        return x + L.swiglu(p["mlp"], h2, rt)
    if kind == "mlstm":
        return x + L.mlstm_block_train(p["mlstm"], h, n_heads=cfg.num_heads,
                                       eps=eps, rt=rt)
    if kind == "slstm":
        a = L.slstm_block_train(p["slstm"], h, n_heads=cfg.num_heads,
                                eps=eps, rt=rt)
        x = x + a
        h2 = L.rms_norm(x, p["ln2"], eps)
        return x + L.swiglu(p["mlp"], h2, rt)
    raise ValueError(kind)


def block_cache_specs(cfg: ArchConfig, kind: str, batch: int,
                      max_len: int) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    if kind in ("attn", "attn_dense", "local_attn"):
        if cfg.mla is not None:
            m = cfg.mla
            c: Dict[str, Any] = {
                "ckv": Spec((batch, max_len, m.kv_lora_rank),
                            ("batch", "kv_seq", None), "zeros", "bf16"),
                "krope": Spec((batch, max_len, m.qk_rope_head_dim),
                              ("batch", "kv_seq", None), "zeros", "bf16"),
            }
            return c
        s_len = min(cfg.local_window, max_len) if kind == "local_attn" \
            else max_len
        return {
            "k": Spec((batch, s_len, cfg.num_kv_heads, hd),
                      ("batch", "kv_seq", "kv_heads", None), "zeros",
                      "bf16"),
            "v": Spec((batch, s_len, cfg.num_kv_heads, hd),
                      ("batch", "kv_seq", "kv_heads", None), "zeros",
                      "bf16"),
        }
    if kind == "rglru":
        w = cfg.lru_width or d
        return {
            "h": Spec((batch, w), ("batch", "lru"), "zeros", "f32"),
            "conv": Spec((batch, cfg.conv1d_width - 1, w),
                         ("batch", None, "lru"), "zeros", "f32"),
        }
    if kind == "mlstm":
        u = 2 * d
        uhd = u // cfg.num_heads
        return {
            "C": Spec((batch, cfg.num_heads, uhd, uhd),
                      ("batch", None, None, "mlstm_state"), "zeros", "f32"),
            "n": Spec((batch, cfg.num_heads, uhd),
                      ("batch", None, "mlstm_state"), "zeros", "f32"),
            "m": Spec((batch, cfg.num_heads), ("batch", None), "zeros",
                      "f32"),
        }
    if kind == "slstm":
        return {k: Spec((batch, d), ("batch", None), "zeros", "f32")
                for k in ("h", "c", "n", "m")}
    raise ValueError(kind)


def block_apply_decode(cfg: ArchConfig, kind: str, p: Params, x: jax.Array,
                       cache: Dict[str, jax.Array], pos: jax.Array,
                       rt: Runtime) -> Tuple[jax.Array, Dict]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    eps = cfg.norm_eps
    h = L.rms_norm(x, p["ln1"], eps)
    if kind in ("attn", "attn_dense", "local_attn"):
        window = cfg.local_window if kind == "local_attn" else 0
        if cfg.mla is not None:
            m = cfg.mla
            a, cache = L.mla_attention_decode(
                p["attn"], h, cache, pos, n_heads=cfg.num_heads,
                kv_lora=m.kv_lora_rank, nope=m.qk_nope_head_dim,
                rope_d=m.qk_rope_head_dim, v_hd=m.v_head_dim,
                rope_theta=cfg.rope_theta, eps=eps, rt=rt)
        else:
            a, cache = L.gqa_attention_decode(
                p["attn"], h, cache, pos, n_heads=cfg.num_heads,
                n_kv=cfg.num_kv_heads, hd=hd, rope_theta=cfg.rope_theta,
                rt=rt, window=window)
        x = x + a
        h2 = L.rms_norm(x, p["ln2"], eps)
        if "moe" in p:
            m = cfg.moe
            y = L.moe_block(p["moe"], h2, n_experts=m.num_experts,
                            top_k=m.top_k,
                            normalize_gates=m.norm_topk_prob, rt=rt)
        else:
            y = L.swiglu(p["mlp"], h2, rt)
        return x + y, cache
    if kind == "rglru":
        a, cache = L.rglru_block_decode(p["rglru"], h, cache,
                                        n_heads=cfg.num_heads, rt=rt)
        x = x + a
        h2 = L.rms_norm(x, p["ln2"], eps)
        return x + L.swiglu(p["mlp"], h2, rt), cache
    if kind == "mlstm":
        a, cache = L.mlstm_block_decode(p["mlstm"], h, cache,
                                        n_heads=cfg.num_heads, eps=eps, rt=rt)
        return x + a, cache
    if kind == "slstm":
        a, cache = L.slstm_block_decode(p["slstm"], h, cache,
                                        n_heads=cfg.num_heads, eps=eps, rt=rt)
        x = x + a
        h2 = L.rms_norm(x, p["ln2"], eps)
        return x + L.swiglu(p["mlp"], h2, rt), cache
    raise ValueError(kind)


# ================================================================= the model

def padded_vocab(v: int) -> int:
    """Pad the vocabulary to a multiple of 256 (lane-aligned and divisible
    by the 16-wide model axis) — standard production embedding padding.
    Padded logit columns are masked to -inf before the softmax/CE."""
    return -(-v // 256) * 256


class DecoderLM:
    """Pure-pytree decoder LM with scan-over-layers groups."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.groups = plan_groups(cfg)
        self.v_pad = padded_vocab(cfg.vocab_size)

    # ----------------------------------------------------------- param specs
    def param_specs(self) -> PyTree:
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "embed": Spec((self.v_pad, cfg.d_model), ("vocab", "embed")),
            "final_norm": Spec((cfg.d_model,), ("embed",), "ones"),
            "groups": [],
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = Spec((cfg.d_model, self.v_pad),
                                    ("embed", "vocab"))
        for g in self.groups:
            unit = [block_specs(cfg, kind) for kind in g.unit]
            if g.repeats > 1:
                unit = [L.stack_specs(u, g.repeats) for u in unit]
            specs["groups"].append(unit)
        return specs

    def init(self, key: jax.Array, rt: Runtime) -> Params:
        return L.init_params(self.param_specs(), key, rt.param_dtype)

    # -------------------------------------------------------------- forward
    def _embed_inputs(self, params: Params, batch: Dict[str, jax.Array],
                      rt: Runtime) -> jax.Array:
        cfg = self.cfg
        tok = batch["tokens"]
        x = params["embed"].astype(rt.compute_dtype)[tok]
        if cfg.family == "hybrid":          # recurrentgemma scales embeddings
            x = x * jnp.asarray(math.sqrt(cfg.d_model), rt.compute_dtype)
        if cfg.frontend == "vit_stub" and "patch_embeds" in batch:
            x = jnp.concatenate(
                [batch["patch_embeds"].astype(rt.compute_dtype), x], axis=1)
        return rt.shard(x, "batch", None, None)

    def forward(self, params: Params, batch: Dict[str, jax.Array],
                rt: Runtime, last_only: bool = False) -> jax.Array:
        """Full-sequence forward -> logits [B, S_total, V] (or [B, 1, V]
        when `last_only` — serving prefill needs only the sampler input,
        and the full fp32 logits of a 32k sequence are GBs)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch, rt)

        for g, gparams in zip(self.groups, params["groups"]):
            def unit_body(x, unit_params, _g=g):
                for kind, p in zip(_g.unit, unit_params):
                    x = block_apply_train(self.cfg, kind, p, x, rt)
                return x
            if rt.remat == "full":
                unit_body = jax.checkpoint(unit_body)
            elif rt.remat == "dots":
                unit_body = jax.checkpoint(
                    unit_body,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            if g.repeats > 1:
                def scan_step(x, up, _f=unit_body):
                    return _f(x, up), None
                x, _ = jax.lax.scan(scan_step, x, gparams)
            else:
                x = unit_body(x, gparams)

        if last_only:
            x = x[:, -1:]
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", x,
                            head.astype(rt.compute_dtype),
                            preferred_element_type=jnp.float32)
        logits = logits.astype(rt.compute_dtype)   # bf16 resident, f32 math
        logits = self._mask_pad(logits)
        return rt.shard(logits, "batch", None, "vocab")

    def _mask_pad(self, logits: jax.Array) -> jax.Array:
        if self.v_pad == self.cfg.vocab_size:
            return logits
        pad = jnp.arange(self.v_pad) >= self.cfg.vocab_size
        return jnp.where(pad, jnp.asarray(-1e9, logits.dtype), logits)

    def loss(self, params: Params, batch: Dict[str, jax.Array],
             rt: Runtime) -> jax.Array:
        """Next-token cross-entropy (fp32), masking non-text prefix."""
        logits = self.forward(params, batch, rt)
        tok = batch["tokens"]
        prefix = logits.shape[1] - tok.shape[1]       # vlm patch positions
        logits = logits[:, prefix:]
        nll = cross_entropy(logits[:, :-1], tok[:, 1:], rt)
        mask = batch.get("loss_mask")
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
        return nll.mean()

    # --------------------------------------------------------------- decode
    #
    # The decode path is UNROLLED over layers with a FLAT per-layer cache
    # list (no scan + stacked cache): a scan's stacked new-cache buffer is
    # re-laid-out by GSPMD at reduced sharding inside the while loop
    # (~0.29 GB/layer/device for a 32k x 8-head cache -> 18 GB at 64
    # layers) and donation cannot alias xs -> ys through the loop.
    # Per-layer cache leaves keep their full mesh sharding and alias
    # in -> out exactly; the decode body is small, so the unrolled HLO
    # stays cheap to compile.
    def cache_specs(self, batch: int, max_len: int) -> PyTree:
        specs: List[Any] = []
        for g in self.groups:
            for _ in range(g.repeats):
                specs.append([block_cache_specs(self.cfg, kind, batch,
                                                max_len)
                              for kind in g.unit])
        return specs

    def init_cache(self, batch: int, max_len: int, rt: Runtime) -> PyTree:
        # recurrent states fp32; KV caches bf16 (set in the cache Specs)
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.resolved_dtype(jnp.bfloat16)),
            self.cache_specs(batch, max_len),
            is_leaf=lambda x: isinstance(x, Spec))

    def decode_step(self, params: Params, cache: PyTree, token: jax.Array,
                    pos: jax.Array, rt: Runtime
                    ) -> Tuple[jax.Array, PyTree]:
        """One decode step: token [B, 1] int32, pos scalar int32."""
        cfg = self.cfg
        x = params["embed"].astype(rt.compute_dtype)[token]
        if cfg.family == "hybrid":
            x = x * jnp.asarray(math.sqrt(cfg.d_model), rt.compute_dtype)
        x = rt.shard(x, "batch", None, None)

        new_caches: List[Any] = []
        li = 0
        for g, gparams in zip(self.groups, params["groups"]):
            for r in range(g.repeats):
                unit_cache = cache[li]
                new_uc = []
                for kind, p, c in zip(g.unit, gparams, unit_cache):
                    if g.repeats > 1:      # static slice of stacked params
                        p = jax.tree.map(lambda t, _r=r: t[_r], p)
                    x, c = block_apply_decode(cfg, kind, p, x, c, pos, rt)
                    new_uc.append(c)
                new_caches.append(new_uc)
                li += 1

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(rt.compute_dtype),
                            preferred_element_type=jnp.float32)
        logits = self._mask_pad(logits)
        return rt.shard(logits, "batch", None, "vocab"), new_caches
