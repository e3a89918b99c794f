"""`DecoderLM` on deepseek-v2-lite-16b's SMOKE config against the plain
float32 reference (`repro.models.deepseek_ref`) on seeded random weights:
the full forward, prefill then token-by-token decode through the latent
cache, and the dropless MoE under routing that sends every token to one
expert.

Tolerance: the logits are about 0.65 at most (0.13 on average).  At
float32 the model and the reference differ only in the order of float32
sums (absorbed against expanded MLA, grouped against dense experts):
3e-7 at most.  ATOL = 1e-5 leaves 30x room above that and sits 400x below
what bfloat16 activations give (4e-3), so computing in bfloat16, the
precision below the float32 this comparison states, fails it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import deepseek_ref as ref
from repro.models import layers as L
from repro.models.layers import Runtime, Spec
from repro.models.lm import DecoderLM

CFG = configs.get_smoke("deepseek-v2-lite-16b")
ATOL = 1e-5
B, S, PROMPT = 2, 16, 8

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


@pytest.mark.parametrize("dtype, agrees", DTYPES)
def test_forward_matches_reference(setup, dtype, agrees):
    model, params, tokens, want = setup
    logits = model.forward(params, {"tokens": tokens},
                           Runtime(compute_dtype=dtype))
    assert (_gap(logits, want) <= ATOL) == agrees


@pytest.mark.parametrize("dtype, agrees", DTYPES)
def test_prefill_then_decode_matches_reference(setup, dtype, agrees):
    """The prompt's logits from the full forward, then every position
    decoded one token at a time through the MLA latent cache (the prompt
    filling it first, as the serving loop does), against the reference's
    full forward over the whole sequence.  The cache is float32: the
    reference stores nothing, and the bfloat16 serving cache is a storage
    choice that `test_decode_parity.py` covers."""
    model, params, tokens, want = setup
    rt = Runtime(compute_dtype=dtype)
    prefill = model.forward(params, {"tokens": tokens[:, :PROMPT]}, rt)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                         model.cache_specs(B, 32),
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


def test_moe_routing_every_token_to_one_expert_drops_none():
    """A router biased so that expert 0 is among every token's top-k: one
    expert takes all 64 tokens (a capacity of ceil(T*k/E*1.25) slots,
    rounded up to 24, would drop 40), and the block still equals the
    reference."""
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
                    rt=Runtime(compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.moe, static_argnums=2)(p, x, CFG)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=0, atol=ATOL)
