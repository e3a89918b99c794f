"""Compiles for a described (not attached) TPU v5e chip.

Nothing runs: each test lowers a program of the main path for one v5e
chip and has the TPU compiler accept it, which catches what the compiler
refuses (unsupported lowering, shapes, memory) without a chip.  The
topology is described inside a fixture only, so that importing this file
never loads the TPU library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.costmodel import _FAST_FIELDS
from repro.core.multiapp import AppSpec
from repro.core.space import default_space
from repro.kernels import ops
from repro.kernels.costmodel import (_COL_FIELDS, FusedJaxScorer,
                                     _fused_jit)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without that chip: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("bucket", [256, 4096])
def test_fused_scorer_compiles_for_v5e(bucket, one_chip, no_compile_cache):
    """The `backend="jax"` scorer's program (x64, op tables as arguments)
    for a traced zoo app at a padded pool bucket."""
    spec = AppSpec.from_app("qwen2-0.5b:decode")
    space = default_space()
    scorer = FusedJaxScorer(spec.stream, space.hw, spec.peak_weight_bits,
                            spec.peak_input_bits, domains=space.domains)
    app, nvals = scorer._app_args()
    pool = tuple(np.zeros((bucket, len(f)), dtype=np.int64)
                 for f in (_FAST_FIELDS, _COL_FIELDS))
    with jax.enable_x64(True):
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                           sharding=one_chip),
            (*app, *pool))
        compiled = _fused_jit.lower(*args, nvals=nvals).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    header = compiled.as_text().split("\n", 1)[0]
    assert header.startswith("HloModule jit_fused_jax_score")
    # the padded pool's 64-bit GOPS vector comes back
    assert f"->f64[{bucket}]" in header


def test_pallas_matmul_compiles_for_v5e(one_chip, no_compile_cache):
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=one_chip)
    compiled = ops.matmul.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
