"""The plain reference equals the program's `numpy-ref` path exactly, so
a later change to `core/costmodel.py` that drifts fails here instead of
moving the yardstick."""

import dataclasses

import numpy as np
import pytest

from bench import reference


def _pool(space, n, seed):
    from repro.core.costmodel import ConfigBatch
    codec = space.codec()
    idx = codec.sample_indices(np.random.default_rng(seed), n)
    return ConfigBatch.from_columns(**codec.decode_values(idx))


@pytest.mark.parametrize("app", ["resnet", "qwen2.5-32b:decode"])
def test_reference_matches_numpy_ref_exactly(app):
    from repro.core.costmodel import area_many, performance_gops
    from repro.core.multiapp import AppSpec
    from repro.core.space import default_space
    space = default_space()
    spec = AppSpec.from_app(app)
    batch = _pool(space, 4096, seed=12)
    hw = dataclasses.asdict(space.hw)
    cols = {f: batch.col(f) for f in reference.CONFIG_FIELDS}
    ops = {f: np.asarray(getattr(spec.stream, f)).ravel()
           for f in reference.OP_FIELDS}
    want = performance_gops(batch, spec.stream, space.hw,
                            spec.peak_weight_bits, spec.peak_input_bits,
                            backend="numpy-ref")
    got = reference.gops(cols, ops, hw, spec.peak_weight_bits,
                         spec.peak_input_bits)
    assert (want > 0).any() and (want == 0).any()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(reference.area(cols, hw),
                                  area_many(batch, space.hw))

    # the float32 control departs from it
    low = reference.gops(cols, ops, hw, spec.peak_weight_bits,
                         spec.peak_input_bits, float_dtype=np.float32)
    np.testing.assert_array_equal(low > 0, want > 0)
    assert not np.array_equal(low, want)
    assert not np.array_equal(reference.area(cols, hw, np.float32),
                              reference.area(cols, hw))


def test_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(reference))
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom) and n.module]
    assert not [m for m in names if m.split(".")[0] in ("repro", "bench")]
