"""jaxpr graph-capture frontend (repro.frontend): lowering parity against
the hand-built graph DSL, sub-jaxpr handling, and the model-zoo workloads."""

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro import configs
from repro.core import apps
from repro.core.apps import _B
from repro.core.costmodel import OpKind
from repro.core.multiapp import AppSpec
from repro.core.search import optimize_for_app
from repro.core.space import default_space
from repro.frontend import trace_to_graph
from repro.frontend.zoo import DECODE_CACHE, PREFILL_SEQ
from repro.models.layers import Runtime


def _op_sig(op):
    return (op.kind, op.nif, op.nix, op.niy, op.nkx, op.nky, op.nof,
            op.nox, op.noy, op.s, op.batch, op.repeat)


def _stream_nodes(graph):
    return [graph.nodes[n] for n in graph.operation_stream()
            if graph.nodes[n].op is not None]


# --------------------------------------------------------------- parity

def test_traced_cnn_matches_hand_built_graph():
    """Op-for-op parity: a tiny JAX CNN lowers to exactly the graph the
    `_B` DSL hand-builds — same kinds, same Table-1 loop bounds, same
    weight/output bits, same Fig. 5 peak activation."""
    H = W = 16
    params = {
        "w1": jax.ShapeDtypeStruct((8, 3, 3, 3), jnp.float32),    # OIHW
        "wd": jax.ShapeDtypeStruct((8, 1, 3, 3), jnp.float32),    # depthwise
        "w2": jax.ShapeDtypeStruct((16, 8, 1, 1), jnp.float32),   # 1x1
        "w3": jax.ShapeDtypeStruct((16, 16, 3, 3), jnp.float32),
        "wfc": jax.ShapeDtypeStruct((16 * 10 * 10, 10), jnp.float32),
    }
    x = jax.ShapeDtypeStruct((1, 3, H, W), jnp.float32)
    dn = ("NCHW", "OIHW", "NCHW")

    def fn(p, x):
        y = lax.conv_general_dilated(x, p["w1"], (1, 1), "VALID",
                                     dimension_numbers=dn)
        y = jax.nn.relu(y)
        y = lax.conv_general_dilated(y, p["wd"], (1, 1), "VALID",
                                     dimension_numbers=dn,
                                     feature_group_count=8)
        y = lax.conv_general_dilated(y, p["w2"], (1, 1), "VALID",
                                     dimension_numbers=dn)
        y = jax.nn.relu(y)
        y = lax.conv_general_dilated(y, p["w3"], (1, 1), "VALID",
                                     dimension_numbers=dn)
        return y.reshape(1, -1) @ p["wfc"]

    traced = trace_to_graph(fn, params, x, name="cnn", bit_width=8)

    b = _B("cnn", H, W, 3)
    b.conv(8, 3, 1, "valid")
    b.dwconv(3, 1, "valid")
    b.conv(16, 1, 1, "valid")
    b.conv(16, 3, 1, "valid")
    b.fc(10)
    hand = b.g

    t_nodes, h_nodes = _stream_nodes(traced), _stream_nodes(hand)
    assert len(t_nodes) == len(h_nodes) == 5
    for tn, hn in zip(t_nodes, h_nodes):
        assert _op_sig(tn.op) == _op_sig(hn.op), (tn.name, hn.name)
        assert tn.output_bits == hn.output_bits, (tn.name, hn.name)
        assert tn.weight_bits == hn.weight_bits, (tn.name, hn.name)
    kinds = [n.op.kind for n in t_nodes]
    assert kinds == [OpKind.CONV2D, OpKind.DEPTHWISE_CONV,
                     OpKind.CHANNEL_MIXING, OpKind.CONV2D, OpKind.MATVEC]

    t_prof = traced.memory_profile()
    h_prof = hand.memory_profile()
    assert t_prof.peak_activation_bits == h_prof.peak_activation_bits
    assert t_prof.peak_weight_bits == h_prof.peak_weight_bits
    assert traced.op_stream().total_macs == hand.op_stream().total_macs


def test_matmul_vs_matvec_prefill_decode_dispatch():
    """Row block > 1 -> matmul (prefill); a single activation row ->
    matvec (decode)."""
    w = jax.ShapeDtypeStruct((64, 32), jnp.float32)

    def fn(p, x):
        return x @ p

    prefill = trace_to_graph(fn, w, jax.ShapeDtypeStruct((8, 64),
                                                         jnp.float32),
                             weight_argnums=(0,), name="p")
    decode = trace_to_graph(fn, w, jax.ShapeDtypeStruct((1, 64),
                                                        jnp.float32),
                            weight_argnums=(0,), name="d")
    (p_op,) = [n.op for n in _stream_nodes(prefill)]
    (d_op,) = [n.op for n in _stream_nodes(decode)]
    assert p_op.kind == OpKind.MATMUL and p_op.nix == 8
    assert d_op.kind == OpKind.MATVEC
    # both carry the full weight
    assert _stream_nodes(prefill)[0].weight_bits == 64 * 32 * 8


def test_dot_batch_dims_become_repeat_instances():
    """Attention-style batched contraction: the head dimension maps to
    `repeat` (independent instances), not into the GEMM shape."""
    q = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)   # [heads, S, hd]
    k = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)

    def fn(params, q, k):
        del params
        return jnp.einsum("hqd,hkd->hqk", q, k)

    g = trace_to_graph(fn, {}, q, k, name="attn")
    (op,) = [n.op for n in _stream_nodes(g)]
    assert op.kind == OpKind.MATMUL
    assert op.repeat == 4
    assert (op.nif, op.nix, op.nof) == (32, 16, 16)
    # activation x activation: no parameters attached
    assert _stream_nodes(g)[0].weight_bits == 0


@pytest.mark.parametrize("rows, kind, instances, per_group", [
    (6, OpKind.MATVEC, 6, 1),        # fewer rows than experts (decode)
    (64, OpKind.MATVEC, 64, 1),      # one row per expert
    (768, OpKind.MATMUL, 64, 12),    # prefill: 128 tokens x top-6
    (100, OpKind.MATMUL, 64, 2),     # uneven: rows round up per expert
])
def test_ragged_dot_costs_balanced_routing(rows, kind, instances,
                                           per_group):
    """Grouped expert GEMM [M, D] x [G, D, F] (`lax.ragged_dot`): min(G, M)
    instances of ceil(M / min(G, M)) rows; the whole expert bank's
    parameters attach to it once."""
    G, D, F = 64, 32, 48
    w = jax.ShapeDtypeStruct((G, D, F), jnp.float32)
    x = jax.ShapeDtypeStruct((rows, D), jnp.float32)
    sizes = jax.ShapeDtypeStruct((G,), jnp.int32)

    def fn(p, x, sizes):
        return lax.ragged_dot(x, p, sizes)

    g = trace_to_graph(fn, w, x, sizes, name="moe")
    (node,) = _stream_nodes(g)
    op = node.op
    assert (op.kind, op.repeat, op.nif) == (kind, instances, D)
    if kind == OpKind.MATVEC:
        assert (op.nix, op.nof) == (F, 1)
    else:
        assert (op.nix, op.nof) == (per_group, F)
    assert op.macs == instances * per_group * D * F
    assert node.weight_bits == G * D * F * 8


def _deepseek_plain_macs(variant: str, kv_block: int = 0) -> int:
    """deepseek-v2-lite-16b's MACs from its layer equations.  Decode: one
    token against DECODE_CACHE latent slots (absorbed MLA).  Prefill:
    PREFILL_SEQ tokens (expanded MLA), keys padded to `kv_block` when it
    is given (`blocked_attention` pads them to its KV block), logits of
    the last token only."""
    a = configs.get_arch("deepseek-v2-lite-16b")
    m, e = a.mla, a.moe
    d, h = a.d_model, a.num_heads
    nope, rope_d, lora, vd = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                              m.kv_lora_rank, m.v_head_dim)
    t = 1 if variant == "decode" else PREFILL_SEQ
    proj = t * (d * h * (nope + rope_d)          # W_q
                + d * (lora + rope_d)            # W_dkv and W_kr
                + h * vd * d)                    # W_o
    if variant == "decode":
        ctx = DECODE_CACHE
        attn = proj + h * (nope * lora           # q_nope absorbs W_uk
                           + ctx * (lora + rope_d)   # scores
                           + ctx * lora          # probs x latent
                           + lora * vd)          # W_uv
    else:
        keys = -(-t // kv_block) * kv_block if kv_block else t
        attn = (proj + t * lora * h * (nope + vd)    # W_ukv
                + h * t * keys * (nope + rope_d + vd))
    moe = t * (d * e.num_experts                 # router
               + e.top_k * 3 * d * e.d_expert    # routed experts
               + 3 * d * e.d_expert * e.num_shared)
    dense = t * 3 * d * e.dense_d_ff
    head = d * a.vocab_size
    return (e.first_dense * (attn + dense)
            + (a.num_layers - e.first_dense) * (attn + moe) + head)


def test_deepseek_decode_costs_the_plain_count():
    """2,511,470,592 MACs; each MoE layer's routed GEMMs are 6 matvec
    instances (the top-6), no op spans all 64 experts."""
    stream = apps.build_app("deepseek-v2-lite-16b:decode").op_stream()
    assert stream.total_macs == _deepseek_plain_macs("decode") \
        == 2_511_470_592
    a = configs.get_arch("deepseek-v2-lite-16b")
    experts = [op for op in stream.ops if op.repeat == a.moe.top_k]
    assert len(experts) == 3 * (a.num_layers - a.moe.first_dense)
    assert all(op.kind == OpKind.MATVEC for op in experts)
    assert not [op for op in stream.ops if op.repeat == a.moe.num_experts]


def test_deepseek_prefill_costs_the_plain_count():
    """305,253,056,512 MACs: the plain 289,398,587,392 plus
    15,854,469,120 of keys padded from 128 to the 1024-key block;
    expert GEMMs are 64 instances of 12 rows (768 routed rows)."""
    stream = apps.build_app("deepseek-v2-lite-16b:prefill").op_stream()
    block = Runtime().attn_kv_block
    assert stream.total_macs == _deepseek_plain_macs("prefill", block) \
        == 305_253_056_512
    assert _deepseek_plain_macs("prefill") == 289_398_587_392
    a = configs.get_arch("deepseek-v2-lite-16b")
    experts = [op for op in stream.ops if op.repeat == a.moe.num_experts
               and op.nix == 12]
    assert len(experts) == 3 * (a.num_layers - a.moe.first_dense)
    assert all(op.kind == OpKind.MATMUL for op in experts)


def test_scan_pjit_remat_are_traversed():
    """Sub-jaxprs (jit, checkpoint) are inlined and scan bodies unrolled
    with per-iteration weight slices."""
    n_layers, d = 3, 16
    stacked = jax.ShapeDtypeStruct((n_layers, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((4, d), jnp.float32)

    @jax.jit
    def layer(x, w):
        return jnp.tanh(x @ w)

    def fn(ws, x):
        def body(carry, w):
            return jax.checkpoint(layer)(carry, w), ()
        out, _ = lax.scan(body, x, ws)
        return out

    g = trace_to_graph(fn, stacked, x, name="scanned")
    ops = [n.op for n in _stream_nodes(g)]
    assert len(ops) == n_layers                 # one matmul per layer
    assert all(op.kind == OpKind.MATMUL for op in ops)
    # each layer carries its own d x d weight slice
    assert all(n.weight_bits == d * d * 8 for n in _stream_nodes(g))


def test_weights_never_become_activation_nodes():
    """Parameter pytrees stay out of the liveness analysis: peak
    activation is independent of the parameter count."""
    small = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    big = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32),
           "unused_style_extra": jax.ShapeDtypeStruct((4096, 4096),
                                                      jnp.float32)}
    x = jax.ShapeDtypeStruct((2, 8), jnp.float32)

    def fn(p, x):
        return x @ p["w"]

    peak_small = trace_to_graph(fn, small, x).memory_profile()
    peak_big = trace_to_graph(fn, big, x).memory_profile()
    assert peak_small.peak_activation_bits == peak_big.peak_activation_bits


# ------------------------------------------------------------------ zoo

ZOO_SIX = [
    "qwen2-0.5b:prefill",
    "qwen2-0.5b:decode",
    "internvl2-1b:prefill",
    "olmoe-1b-7b:prefill",
    "whisper-medium:prefill",
    "xlstm-1.3b:prefill",
]


@pytest.mark.parametrize("name", ZOO_SIX)
def test_zoo_workloads_build(name):
    g = apps.build_app(name)
    s = g.summary()
    assert s["total_macs"] > 0
    assert s["n_ops"] > 0
    assert s["peak_input_memory_bytes"] > 0
    # weights roughly track the architecture's analytic parameter count
    assert s["total_weight_bytes"] > 1e6


def test_zoo_decode_is_matvec_shaped():
    s = apps.build_app("qwen2-0.5b:decode").summary()
    assert s["op_counts"]["matvec"] > s["op_counts"].get("matmul", 0)
    p = apps.build_app("qwen2-0.5b:prefill").summary()
    assert p["op_counts"]["matmul"] > p["op_counts"].get("matvec", 0)


def test_zoo_apps_listed_and_unknown_rejected():
    names = apps.all_app_names()
    assert set(apps.APP_NAMES) <= set(names)
    assert set(ZOO_SIX) <= set(names)
    assert apps.zoo_app_names()
    with pytest.raises(KeyError):
        apps.build_app("definitely-not-an-app")
    with pytest.raises(KeyError):
        apps.build_app("qwen2-0.5b:bogus-variant")


@pytest.mark.parametrize("engine", ["greedy", "anneal", "genetic", "random"])
def test_zoo_optimize_every_engine_nonzero_gops(engine):
    """Acceptance: traced workloads drive the full DSE — every engine
    finds a valid nonzero-GOPS config at the default area budget."""
    space = default_space()
    for name in ("qwen2-0.5b:prefill", "internvl2-1b:prefill",
                 "qwen2-0.5b:decode"):
        spec = AppSpec.from_graph(name, apps.build_app(name))
        res = optimize_for_app(
            spec.stream, space, engine=engine, k=1, restarts=1, seed=0,
            max_rounds=4, peak_weight_bits=spec.peak_weight_bits,
            peak_input_bits=spec.peak_input_bits,
            engine_kwargs={"population": 24, "chains": 6, "batch": 32})
        assert res.best_perf > 0, (name, engine)
        assert res.best.area(space.hw) <= space.area_budget
