"""Integration: the dry-run machinery on a small forced-device mesh.

Runs in a subprocess because XLA_FLAGS must be set before jax initializes
(the main pytest process already holds a single-device backend).
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro import configs
    from repro.configs.shapes import ShapeSpec
    from repro.core.roofline import measure_compiled
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_step_bundle

    assert len(jax.devices()) == 8
    mesh = make_mesh((2, 4), ("data", "model"))
    arch = configs.get_smoke("qwen2-0.5b")
    shape = ShapeSpec("tiny_train", seq_len=64, global_batch=8, mode="train")
    bundle = build_step_bundle(arch, shape, mesh, microbatches=2)
    with mesh:
        compiled = bundle.lower().compile()
        flops, hbm, coll, peak = measure_compiled(compiled)
    out = {"flops": flops, "hbm": hbm, "coll": coll.total_bytes,
           "peak": peak, "kinds": coll.by_kind}
    print("RESULT " + json.dumps(out))

    # decode path on the same mesh
    shape_d = ShapeSpec("tiny_decode", seq_len=64, global_batch=8,
                        mode="decode")
    bundle_d = build_step_bundle(arch, shape_d, mesh)
    with mesh:
        compiled_d = bundle_d.lower().compile()
        f2, h2, c2, p2 = measure_compiled(compiled_d)
    print("RESULT2 " + json.dumps({"flops": f2, "coll": c2.total_bytes}))
""")


@pytest.mark.slow
def test_small_mesh_dryrun_end_to_end():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")]
    assert len(lines) == 2
    res = json.loads(lines[0].split(" ", 1)[1])
    assert res["flops"] > 0
    assert res["hbm"] > 0
    assert res["coll"] > 0            # sharded training must communicate
    assert res["peak"] > 0
    res2 = json.loads(lines[1].split(" ", 1)[1])
    assert res2["flops"] > 0
