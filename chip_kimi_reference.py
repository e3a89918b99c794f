"""Chip check of the kimi-linear-48b-a3b model against its plain reference.

    python chip_kimi_reference.py [--smoke]

Run from the root of a checkout on a machine with a TPU (about 4.7 GB of
float32 weights; never at full size on a shared CPU).  `DecoderLM` and
`repro.models.kimi_linear_ref` on the same seeded weights at the published
widths, cut as the model-configs guide's section 4 allows: depth to
[KDA+dense, KDA+MoE, MLA+MoE], 64 of the 256 experts held (the router at
its full width, so both sides compute that share of each MoE layer), the
vocabulary's first 20480 rows.  A 128-token prefill (the chunked KDA
form), then decode through the KDA state, its conv history and the MLA
latent cache at every position from 0 to 143 (the last 16 beyond the
prompt).  Logits are compared with `tests/test_kimi_linear_reference.py`'s
tolerance, both sides in float32 under `highest` matmul precision, then
with bfloat16 activations, which must miss it.  The last stdout line is a
JSON report with `ok`; the exit code is 0 when `ok`.  `--smoke` runs the
SMOKE config instead (a CPU rehearsal)."""

import dataclasses
import json
import sys
import time

sys.path[0:0] = ["src"]            # the checkout's own `repro`

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import kimi_linear_ref as ref
from repro.models import layers as L
from repro.models.layers import Runtime, Spec
from repro.models.lm import DecoderLM

ATOL = 1e-5
smoke = "--smoke" in sys.argv
t0 = time.time()
if smoke:
    cfg = configs.get_smoke("kimi-linear-48b-a3b")
    held, prompt, extra, vocab = 4, 24, 8, 512
else:
    cfg = configs.get_arch("kimi-linear-48b-a3b")
    held, prompt, extra, vocab = 64, 128, 16, 20480
cfg = dataclasses.replace(
    cfg, num_layers=3, vocab_size=vocab,
    kda=dataclasses.replace(cfg.kda, layers=(1, 2)))
assert cfg.layer_plan() == (("kda", "dense"), ("kda", "moe"), ("mla", "moe"))
model = DecoderLM(cfg)


def held_share(spec):
    if isinstance(spec, Spec) and spec.axes[:1] == ("experts",):
        return Spec((held,) + spec.shape[1:], spec.axes, spec.init,
                    spec.dtype)
    return spec


specs = jax.tree.map(held_share, model.param_specs(),
                     is_leaf=lambda s: isinstance(s, Spec))
params = L.init_params(specs, jax.random.PRNGKey(19))
n_params = sum(int(np.prod(t.shape)) for t in jax.tree.leaves(params))
seq = prompt + extra
tokens = jax.random.randint(jax.random.PRNGKey(20), (1, seq), 0, vocab)
report = {"device": jax.devices()[0].device_kind, "params": n_params,
          "param_bytes": 4 * n_params, "held_experts": held,
          "router_width": cfg.moe.num_experts, "prompt": prompt,
          "decoded": seq}
with jax.default_matmul_precision("highest"):
    want = np.asarray(jax.jit(ref.forward, static_argnums=2)(
        params, tokens, cfg))[..., :vocab]
report["ref_s"] = time.time() - t0
report["logit_max"] = float(np.abs(want).max())
report["logit_mean"] = float(np.abs(want).mean())

for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
    t1 = time.time()
    rt = Runtime(compute_dtype=dtype)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t}, rt))
        pre = np.asarray(fwd(params, tokens[:, :prompt]).astype(
            jnp.float32))[..., :vocab]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                             model.cache_specs(1, seq),
                             is_leaf=lambda x: isinstance(x, Spec))
        step = jax.jit(lambda p, c, tok, pos: model.decode_step(
            p, c, tok, pos, rt))
        outs = []
        for t in range(seq):
            lg, cache = step(params, cache, tokens[:, t:t + 1], jnp.int32(t))
            outs.append(lg[:, 0])
        dec = np.asarray(jnp.stack(outs, 1).astype(jnp.float32))[..., :vocab]
    gaps = {"prefill": float(np.abs(pre - want[:, :prompt]).max()),
            "decode_prompt": float(np.abs(dec[:, :prompt]
                                          - want[:, :prompt]).max()),
            "decode_beyond": float(np.abs(dec[:, prompt:]
                                          - want[:, prompt:]).max())}
    report[name] = dict(gaps, agrees=max(gaps.values()) <= ATOL,
                        seconds=time.time() - t1)
    print(json.dumps({name: report[name]}), flush=True)

report["ok"] = report["float32"]["agrees"] and not report["bfloat16"]["agrees"]
report["peak_bytes"] = int((jax.devices()[0].memory_stats() or {}).get(
    "peak_bytes_in_use", 0))
report["total_s"] = time.time() - t0
print(json.dumps(report), flush=True)
sys.exit(0 if report["ok"] else 1)
