"""Kernel microbenches + tile-model predictions.

Wall times here are CPU interpret-mode (correctness harness), NOT TPU
numbers; the *derived* column is the tile cost model's predicted v5e
latency for the production shape — the quantity the DSE optimizes.

`--smoke` runs every kernel once at reduced shapes and exits nonzero on
any correctness failure — the CI lowering check for the Pallas kernels
(interpret mode on CPU; the same code lowers for real on TPU/GPU).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernel_tune import tile_cost, TileConfig, tune_matmul_tiles
from repro.kernels import ops


def _time(fn, *args, n=3, **kw):
    fn(*args, **kw).block_until_ready()
    t0 = time.time()
    for _ in range(n):
        out = fn(*args, **kw)
    out.block_until_ready()
    return (time.time() - t0) / n * 1e6


def run(verbose: bool = True) -> list:
    rows = []
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)

    # matmul: CPU-interpret correctness timing + v5e tile-model prediction
    x = jax.random.normal(k1, (256, 512), jnp.float32)
    y = jax.random.normal(k2, (512, 256), jnp.float32)
    us = _time(ops.matmul, x, y, bm=128, bk=128, bn=128, interpret=True)
    best, cost, _ = tune_matmul_tiles(8192, 8192, 8192)
    rows.append(("matmul_interp_256x512x256", us,
                 f"v5e_pred_8k^3_tile=({best.bm},{best.bk},{best.bn})_"
                 f"{cost['latency_s']*1e3:.2f}ms"))

    q = jax.random.normal(k1, (1, 256, 4, 64), jnp.float32)
    kk = jax.random.normal(k2, (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(k3, (1, 256, 2, 64), jnp.float32)
    us = _time(ops.flash_attention, q, kk, v, causal=True, bq=128, bkv=128,
               interpret=True)
    # causal tile skipping halves the MXU work vs dense
    rows.append(("flash_attn_interp_s256", us, "causal_tile_skip=2x_flops"))

    a = jax.random.uniform(k1, (1, 512, 256), jnp.float32, 0.8, 0.999)
    b = jax.random.normal(k2, (1, 512, 256), jnp.float32)
    us = _time(ops.rglru_scan, a, b, bs=128, bw=256, interpret=True)
    rows.append(("rglru_scan_interp_s512", us,
                 "log_step_doubling=7_steps_per_128tile"))

    if verbose:
        print("name,us_per_call,derived")
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}")
    return rows


def run_smoke(verbose: bool = True) -> None:
    """One pass per kernel at small shapes, correctness asserted — the CI
    Pallas lowering check (interpret mode on CPU)."""
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)

    x = jax.random.normal(k1, (128, 128), jnp.float32)
    y = jax.random.normal(k2, (128, 128), jnp.float32)
    got = ops.matmul(x, y, bm=128, bk=128, bn=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x) @ np.asarray(y),
                               rtol=1e-5, atol=1e-5)

    q = jax.random.normal(k1, (1, 128, 2, 64), jnp.float32)
    kk = jax.random.normal(k2, (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(k3, (1, 128, 2, 64), jnp.float32)
    out = ops.flash_attention(q, kk, v, causal=True, bq=128, bkv=128,
                              interpret=True)
    assert np.isfinite(np.asarray(out)).all()

    a = jax.random.uniform(k1, (1, 128, 256), jnp.float32, 0.8, 0.999)
    b = jax.random.normal(k2, (1, 128, 256), jnp.float32)
    out = ops.rglru_scan(a, b, bs=128, bw=256, interpret=True)
    assert np.isfinite(np.asarray(out)).all()

    if verbose:
        print("[kernel-smoke] matmul, flash_attention, rglru_scan: OK")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="one correctness pass per kernel (CI mode)")
    args = ap.parse_args()
    if args.smoke:
        run_smoke()
    else:
        run()
