"""MoE block vs. a brute-force dense-dispatch reference (the block is
dropless, so every routed token is computed whatever the routing)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models.layers import Runtime, Spec

RT = Runtime(compute_dtype=jnp.float32)
SRC = str(Path(__file__).resolve().parents[1] / "src")
KEY = jax.random.PRNGKey(3)


def _moe_ref(p, x2d, n_experts, top_k, normalize):
    """Dense reference: every token through its top-k experts, no capacity."""
    logits = x2d @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)
    if normalize:
        gate = gate / gate.sum(-1, keepdims=True)
    y = jnp.zeros_like(x2d)
    for t in range(x2d.shape[0]):
        acc = jnp.zeros(x2d.shape[1])
        for j in range(top_k):
            e = int(idx[t, j])
            h = jax.nn.silu(x2d[t] @ p["we1"][e]) * (x2d[t] @ p["we3"][e])
            acc = acc + gate[t, j] * (h @ p["we2"][e])
        y = y.at[t].set(acc)
    return y


@pytest.mark.parametrize("normalize", [True, False])
def test_moe_matches_dense_reference(normalize):
    D, E, F, k = 16, 8, 24, 2
    specs = L.moe_specs(D, E, F, n_shared=0)
    params = L.init_params(specs, KEY, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, D)) * 0.5
    y = L.moe_block(params, x, n_experts=E, top_k=k,
                    normalize_gates=normalize, rt=RT)
    want = _moe_ref(params, x[0], E, k, normalize)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_moe_shared_expert_added():
    D, E, F, k = 8, 4, 8, 2
    specs = L.moe_specs(D, E, F, n_shared=1)
    params = L.init_params(specs, KEY, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, D))
    y = L.moe_block(params, x, n_experts=E, top_k=k,
                    normalize_gates=False, rt=RT)
    # zero the shared expert -> output changes
    p2 = dict(params)
    p2["shared"] = jax.tree.map(jnp.zeros_like, params["shared"])
    y2 = L.moe_block(p2, x, n_experts=E, top_k=k,
                     normalize_gates=False, rt=RT)
    assert not np.allclose(np.asarray(y), np.asarray(y2))


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import fsdp_rules, tp_rules
    from repro.launch.mesh import make_mesh
    from repro.models import layers as L

    mesh = make_mesh((2, 4), ("data", "model"))
    D, E, F, k = 16, 8, 8, 3              # 2 experts per shard, top-3
    params = L.init_params(L.moe_specs(D, E, F, n_shared=1),
                           jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, D))
    skewed = dict(params, router=params["router"].at[0, 0].set(10.0))
    x_skewed = x.at[..., 0].set(5.0)      # expert 0 in every token's top-k

    def block(rt, p, x):
        return L.moe_block(p, x, n_experts=E, top_k=k,
                           normalize_gates=True, rt=rt)

    def out_and_grad(rt, p, x):
        return (jax.jit(lambda p, x: block(rt, p, x))(p, x),
                jax.jit(jax.grad(lambda p: block(rt, p, x).sum()))(p))

    one = L.Runtime(compute_dtype=jnp.float32)
    cases = {"fsdp": (fsdp_rules(("data",)), params, x),
             "tp": (tp_rules(("data",)), params, x),
             "no_batch_axis": (fsdp_rules(()), params, x),
             "no_experts_axis": (fsdp_rules(("data",)).replace(experts=None),
                                 params, x),
             "all_to_one_expert": (fsdp_rules(("data",)), skewed, x_skewed)}
    out = {}
    for name, (rules, p, xx) in cases.items():
        want, gwant = out_and_grad(one, p, xx)
        with mesh:
            got, g = out_and_grad(L.Runtime(compute_dtype=jnp.float32,
                                            mesh=mesh, rules=rules), p, xx)
        scale = float(jnp.abs(want).max())
        out[name] = {
            "gap": float(jnp.abs(got - want).max()) / scale,
            "grad_gap": max(float(jnp.abs(a - b).max() / jnp.abs(b).max())
                            for a, b in zip(jax.tree.leaves(g),
                                            jax.tree.leaves(gwant)))}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_gaps():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[-1].split(" ", 1)[1])


@pytest.mark.parametrize("case", ["fsdp", "tp", "no_batch_axis",
                                  "no_experts_axis", "all_to_one_expert"])
def test_moe_on_a_mesh_matches_one_device(mesh_gaps, case):
    """On a 2x4 (data, model) mesh each device routes its batch shard to
    its shard of the experts (2 of 8, fewer than top-3, so each device
    dispatches 2 rows per token, not 3) and a psum combines them; output
    and weight gradients equal the one-device block's.  The bound is float32
    rounding of a reordered sum (the psum), relative to the largest
    value; a dropped or doubled slot moves either by O(1)."""
    assert mesh_gaps[case]["gap"] <= 1e-5
    assert mesh_gaps[case]["grad_gap"] <= 1e-5
