"""`DecoderLM` on kimi-linear-48b-a3b's SMOKE config against the plain
float32 reference (`repro.models.kimi_linear_ref`) on seeded random
weights: the full forward, prefill then token-by-token decode through the
KDA state and the MLA latent cache, the chunked KDA form against the
token-by-token recurrence, and the sigmoid-routed MoE.

SMOKE has the published layer kinds: a dense KDA first layer, then KDA,
KDA, MLA twice over (one scan group of two repeats), and a shared expert.
Its 80-token sequences span two 64-token chunks, the second cut short,
and the 70-token prompt ends past the first.

Tolerance: the logits are about 0.75 at most (0.13 on average).  At
float32 the model and the reference differ only in the order of float32
sums (chunked against token-by-token KDA, absorbed against expanded MLA,
grouped against dense experts): 1.5e-6 at most.  ATOL = 1e-5 leaves 6x
room above that and sits about 4000x below what bfloat16 activations give
(4e-2), so computing in bfloat16, the precision below the float32 this
comparison states, fails it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import kimi_linear_ref as ref
from repro.models import layers as L
from repro.models.layers import Runtime, Spec
from repro.models.lm import DecoderLM, plan_groups

CFG = configs.get_smoke("kimi-linear-48b-a3b")
ATOL = 1e-5
STATE_ATOL = 5e-5
B, S, PROMPT = 2, 80, 70

DTYPES = [(jnp.float32, True), (jnp.bfloat16, False)]


@pytest.fixture(scope="module")
def setup():
    model = DecoderLM(CFG)
    params = model.init(jax.random.PRNGKey(0), Runtime())
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                CFG.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(ref.forward, static_argnums=2)(
            params, tokens, CFG))
    return model, params, tokens, want[..., :CFG.vocab_size]


def _gap(got, want) -> float:
    got = np.asarray(jnp.asarray(got, jnp.float32))[..., :CFG.vocab_size]
    return float(np.abs(got - want).max())


def test_smoke_has_the_published_layer_kinds():
    plan = CFG.layer_plan()
    assert plan[0] == ("kda", "dense")
    assert plan[1:] == (("kda", "moe"), ("kda", "moe"), ("mla", "moe")) * 2
    assert [(len(g.unit), g.repeats) for g in plan_groups(CFG)] == \
        [(1, 1), (3, 2)]
    kimi = configs.get_arch("kimi-linear-48b-a3b").layer_plan()
    assert [i + 1 for i, (m, _) in enumerate(kimi) if m == "mla"] == \
        [4, 8, 12, 16, 20, 24, 27]


@pytest.mark.parametrize("dtype, agrees", DTYPES)
def test_forward_matches_reference(setup, dtype, agrees):
    model, params, tokens, want = setup
    logits = model.forward(params, {"tokens": tokens},
                           Runtime(compute_dtype=dtype))
    assert (_gap(logits, want) <= ATOL) == agrees


@pytest.mark.parametrize("dtype, agrees", DTYPES)
def test_prefill_then_decode_matches_reference(setup, dtype, agrees):
    """The prompt's logits from the full (chunked) forward, then every
    position decoded one token at a time through the KDA state, its conv
    history and the MLA latent cache, against the reference's full
    forward over the whole sequence.  The cache is float32: the reference
    stores nothing."""
    model, params, tokens, want = setup
    rt = Runtime(compute_dtype=dtype)
    prefill = model.forward(params, {"tokens": tokens[:, :PROMPT]}, rt)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                         model.cache_specs(B, S),
                         is_leaf=lambda x: isinstance(x, Spec))
    step = jax.jit(lambda c, tok, pos: model.decode_step(params, c, tok,
                                                         pos, rt))
    steps = []
    for t in range(S):
        logits, cache = step(cache, tokens[:, t:t + 1], jnp.int32(t))
        steps.append(logits[:, 0])
    gap = max(_gap(prefill, want[:, :PROMPT]),
              _gap(jnp.stack(steps, axis=1), want))
    assert (gap <= ATOL) == agrees


def _kda_inputs(seq, heads=4, dk=32, dv=16, seed=5):
    """q, k L2-normalised (q scaled), log-decays per channel with the
    published init's rates: -exp(A_log) * softplus(f), A = exp(A_log) in
    [1, 16], f ~ N(0, 2), so a token's log-decay reaches below -100 and a
    64-token chunk's cumulative one far below -88, past the float32 range
    that a factorisation through e^{+G} would need."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (2, seq, heads, dk))
    k = jax.random.normal(ks[1], (2, seq, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, seq, heads, dv))
    a = jax.random.uniform(ks[3], (heads, 1), minval=1.0, maxval=16.0)
    g = -a * jax.nn.softplus(2.0 * jax.random.normal(ks[4],
                                                     (2, seq, heads, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (2, seq, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("seq", [64, 100, 192])
@pytest.mark.parametrize("dtype, agrees", DTYPES)
def test_chunked_kda_matches_the_recurrence(seq, dtype, agrees):
    """The chunked (WY) prefill form at chunk 64 against the token-by-token
    recurrence, outputs and last state, for one chunk, a sequence that is
    not a multiple of the chunk, and three chunks.  The outputs are 0.2 at
    most and float32 agrees to 1.4e-6 (ATOL); the state's entries reach
    0.94 and carry the triangular inverse's rounding of every chunk:
    8.5e-6 at three chunks, so its tolerance is 5e-5.  Inputs rounded to
    bfloat16 miss them by 80x (outputs, 8.3e-4) and 45x (state, 2.2e-3)."""
    q, k, v, g, beta = _kda_inputs(seq)
    assert float(g.min()) < -100
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(ref.kda_recurrence)(q, k, v, g, beta)
        got_o, got_s = jax.jit(L._kda_chunked)(
            *(t.astype(dtype) for t in (q, k, v, g, beta)))
    gap_o = float(jnp.abs(got_o - want_o).max())
    gap_s = float(jnp.abs(got_s - want_s).max())
    assert np.isfinite(gap_o) and np.isfinite(gap_s)
    assert (gap_o <= ATOL) == (gap_s <= STATE_ATOL) == agrees


def test_sigmoid_router_renormalises_and_scales():
    """A router biased so that expert 0 is among every token's top-k: the
    sigmoid scores' top-k gates, renormalised and scaled by 2.446, route
    all 64 tokens to it, and the block equals the reference."""
    m = CFG.moe
    d = CFG.d_model
    p = L.init_params(L.moe_specs(d, m.num_experts, m.d_expert,
                                  m.num_shared), jax.random.PRNGKey(2))
    p["router"] = p["router"].at[0, 0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, d))
    x = x.at[..., 0].set(5.0)
    _, idx = jax.lax.top_k(x @ p["router"], m.top_k)
    assert bool((idx == 0).any(-1).all())
    y = L.moe_block(p, x, n_experts=m.num_experts, top_k=m.top_k,
                    normalize_gates=m.norm_topk_prob,
                    rt=Runtime(compute_dtype=jnp.float32),
                    scoring=m.scoring, scale=m.routed_scale)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.moe, static_argnums=2)(p, x, CFG)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=0, atol=ATOL)


def test_expert_shares_add_up_to_the_whole_layer():
    """One chip's share of a MoE layer: the router at its full width, the
    expert weights sliced.  The routed parts of four shares of two
    experts each, plus the shared expert once, give the uncut reference
    layer; the reference given a share's weights computes that share."""
    m = CFG.moe
    d = CFG.d_model
    p = L.init_params(L.moe_specs(d, m.num_experts, m.d_expert,
                                  m.num_shared), jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, d))
    rt = Runtime(compute_dtype=jnp.float32)
    per = m.num_experts // 4

    def share(i):
        return dict(p, **{w: p[w][i * per:(i + 1) * per]
                          for w in ("we1", "we3", "we2")})

    parts = []
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            q = share(i)
            routed = L._route_experts(
                q, x[0], i * per, top_k=m.top_k,
                normalize_gates=m.norm_topk_prob, cd=jnp.float32,
                scoring=m.scoring, scale=m.routed_scale)
            parts.append(routed)
            if i == 0:      # the reference on the first share's weights
                alone = ref.moe({k: v for k, v in q.items()
                                 if k != "shared"}, x, CFG)
                np.testing.assert_allclose(np.asarray(routed),
                                           np.asarray(alone[0]),
                                           rtol=0, atol=ATOL)
        whole = ref.moe(p, x, CFG)[0]
        shared = L.swiglu(p["shared"], x, rt)[0]
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=0, atol=ATOL)
