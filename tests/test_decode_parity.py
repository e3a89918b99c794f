"""Teacher-forcing parity: full-sequence forward logits must match the
step-by-step decode path (chunkwise/parallel train forms vs. recurrent
decode forms) for every architecture family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.steps import build_model
from repro.models.layers import Runtime

RT = Runtime(compute_dtype=jnp.float32)
KEY = jax.random.PRNGKey(7)

# encdec handled separately (decode consumes precomputed cross-KV)
PARITY_ARCHS = [n for n in configs.ARCH_NAMES
                if n not in ("whisper-medium", "internvl2-1b")]


@pytest.mark.parametrize("name", PARITY_ARCHS)
def test_forward_vs_decode_logits(name):
    cfg = configs.get_smoke(name)
    model = build_model(cfg)
    params = model.init(KEY, RT)
    B, S = 2, 12
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)

    full = model.forward(params, {"tokens": tokens}, RT)      # [B,S,V]

    cache = model.init_cache(B, max_len=32, rt=RT)
    step_logits = []
    for t in range(S):
        lg, cache = model.decode_step(params, cache, tokens[:, t:t + 1],
                                      jnp.int32(t), RT)
        step_logits.append(lg[:, 0])
    dec = jnp.stack(step_logits, axis=1)

    v = cfg.vocab_size
    np.testing.assert_allclose(np.asarray(dec[..., :v]),
                               np.asarray(full[..., :v]),
                               rtol=2e-2, atol=5e-3)


def test_local_attention_window_parity():
    """RG local attention must honour the window in both paths."""
    cfg = configs.get_smoke("recurrentgemma-9b")
    model = build_model(cfg)
    params = model.init(KEY, RT)
    B, S = 1, 24          # > local_window (16) to exercise the ring buffer
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = model.forward(params, {"tokens": tokens}, RT)
    cache = model.init_cache(B, max_len=S, rt=RT)
    outs = []
    for t in range(S):
        lg, cache = model.decode_step(params, cache, tokens[:, t:t + 1],
                                      jnp.int32(t), RT)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    v = cfg.vocab_size
    np.testing.assert_allclose(np.asarray(dec[..., :v]),
                               np.asarray(full[..., :v]),
                               rtol=3e-3, atol=3e-3)
