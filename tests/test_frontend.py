"""jaxpr graph-capture frontend (repro.frontend): lowering parity against
the hand-built graph DSL, sub-jaxpr handling, and the model-zoo workloads."""

import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
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
from repro.models.layers import KDA_CHUNK, Runtime


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


def _kimi_plain_macs(variant: str) -> int:
    """kimi-linear-48b-a3b's MACs from its layer equations.  KDA: the
    projections, the kernel-4 depthwise conv and low-rank gates per token;
    decode adds the state's three dk x dv products per head (k^T S, the
    rank-1 write, S^T q), prefill the chunked form's per chunk and head
    (C = 64, 16-token sub-chunks): the decayed pairs inside sub-chunks
    (k.k and q.k, C*16*dk each), against earlier keys (C^2*dk each), the
    two triangular products (C^2*dv, C^2*dk), the output's P w (C^2*dv),
    and three C*dk*dv products with the state (w, output, state update).
    MLA (no RoPE) as deepseek's: absorbed at decode, expanded with keys
    padded to the 1024-key block at prefill.  MoE: router, top-8 routed
    and one shared expert; layer 1 dense."""
    a = configs.get_arch("kimi-linear-48b-a3b")
    m, e, k = a.mla, a.moe, a.kda
    d, h = a.d_model, a.num_heads
    nope, rope_d, lora, vd = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                              m.kv_lora_rank, m.v_head_dim)
    kh, dk, dv, r, C = (k.num_heads, k.head_dim, k.head_dim, k.gate_rank,
                        KDA_CHUNK)
    w = kh * dk
    t = 1 if variant == "decode" else PREFILL_SEQ
    kda = t * (d * 3 * w + k.conv_size * 3 * w       # W_qkv, short conv
               + 2 * (d * r + r * w)                 # decay and out gates
               + d * kh + w * d)                     # W_beta, W_o
    proj = t * (d * h * (nope + rope_d) + d * (lora + rope_d) + h * vd * d)
    if variant == "decode":
        kda += kh * 3 * dk * dv
        ctx = DECODE_CACHE
        mla = proj + h * (nope * lora + ctx * (lora + rope_d) + ctx * lora
                          + lora * vd)
    else:
        kda += -(-t // C) * kh * (2 * C * 16 * dk + 3 * C * C * dk
                                  + 2 * C * C * dv + 3 * C * dk * dv)
        keys = -(-t // Runtime().attn_kv_block) * Runtime().attn_kv_block
        mla = (proj + t * lora * h * (nope + vd)
               + h * t * keys * (nope + rope_d + vd))
    moe = t * (d * e.num_experts + e.top_k * 3 * d * e.d_expert
               + 3 * d * e.d_expert * e.num_shared)
    n_kda = len(k.layers)
    return (n_kda * kda + (a.num_layers - n_kda) * mla
            + e.first_dense * t * 3 * d * e.dense_d_ff
            + (a.num_layers - e.first_dense) * moe + d * a.vocab_size)


@pytest.mark.parametrize("variant, macs", [("decode", 3_169_402_880),
                                           ("prefill", 366_835_924_992)])
def test_kimi_linear_costs_the_plain_count(variant, macs):
    """Every KDA product that grows with the chunk or the state is a
    costed op: the traced MACs are the layer equations' count (the
    chunked form's are 7,717,519,360 of prefill's, 2.1%)."""
    stream = apps.build_app(f"kimi-linear-48b-a3b:{variant}").op_stream()
    assert stream.total_macs == _kimi_plain_macs(variant) == macs


def test_short_conv_lowers_to_depthwise_conv():
    """The KDA and RG-LRU short conv (`layers._causal_conv1d`): a grouped
    `conv_general_dilated`, costed as Table 1's depthwise row, one filter
    of K taps per channel over the left-padded sequence."""
    from repro.models.layers import _causal_conv1d
    x = jax.ShapeDtypeStruct((1, 16, 24), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 24), jnp.float32)
    g = trace_to_graph(lambda w, x: _causal_conv1d(x, w), w, x, name="sc")
    (node,) = _stream_nodes(g)
    op = node.op
    assert op.kind == OpKind.DEPTHWISE_CONV
    assert (op.nif, op.nix, op.nkx, op.nof, op.nox, op.repeat) == \
        (1, 16 + 3, 4, 1, 16, 24)
    assert op.macs == 16 * 4 * 24
    assert node.weight_bits == 4 * 24 * 8
    stream = apps.build_app("kimi-linear-48b-a3b:decode").op_stream()
    convs = [op for op in stream.ops if op.kind == OpKind.DEPTHWISE_CONV]
    assert [(op.nix, op.nox, op.repeat) for op in convs] == \
        [(4, 1, 3 * 32 * 128)] * 20


def _alive_at_peak(graph):
    """The vertices resident at the Fig. 5 peak (its replay)."""
    remaining = {n: 0 for n in graph.nodes}
    for node in graph.nodes.values():
        for p in node.parents:
            remaining[p] += 1
    alive, best = {}, (-1, {})
    for name in graph.operation_stream():
        node = graph.nodes[name]
        alive[name] = node.output_bits
        if sum(alive.values()) > best[0]:
            best = (sum(alive.values()), dict(alive))
        for p in node.parents:
            remaining[p] -= 1
            if remaining[p] == 0:
                alive.pop(p, None)
        if remaining[name] == 0:
            alive.pop(name, None)
    return best


def test_kimi_decode_floor_is_set_by_a_kda_state():
    """One KDA state is [32, 128, 128] = 4,194,304 bits at 8 bits, over
    seven times a layer's 128-slot latent cache (589,824), so decode's
    Eq. 13 floor is KDA's: at the peak the largest resident vertices are
    states."""
    graph = apps.build_app("kimi-linear-48b-a3b:decode")
    state = 32 * 128 * 128 * 8
    spec = AppSpec.from_graph("kimi-linear-48b-a3b:decode", graph)
    peak, alive = _alive_at_peak(graph)
    assert spec.peak_input_bits == peak >= state
    assert max(alive.values()) == state
    assert sum(b for b in alive.values() if b == state) > peak // 2


BENCH_CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"


@pytest.mark.parametrize("config", ["qwen2.5-32b", "deepseek-v2-lite-16b",
                                    "paper-cnn7"])
def test_benchmark_streams_are_unchanged(config):
    """The op streams the benchmark's configurations record (op count, a
    sha256 of the [11, ops] op table, Eq. 11/13 peaks) are the traced
    ones: the per-layer mixer/ffn plan, the sigmoid router, MLA without
    RoPE and the depthwise short conv leave them byte-identical."""
    conf = json.loads((BENCH_CONFIGS / f"{config}.json").read_text())
    for app in conf["apps"]:
        spec = AppSpec.from_app(app,
                                weight_peak_mode=conf["weight_peak_mode"])
        table = np.ascontiguousarray(spec.stream.field_matrix)
        assert {"ops": table.shape[1],
                "op_table_sha256": hashlib.sha256(table.tobytes()
                                                  ).hexdigest(),
                "peak_weight_bits": spec.peak_weight_bits,
                "peak_input_bits": spec.peak_input_bits} == \
            conf["streams"][app], app


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
