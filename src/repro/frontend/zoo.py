"""Model-zoo DSE workloads: every architecture in `repro.configs` as a
traced `ComputationGraph` app.

The paper's premise is that the *target application* drives the
accelerator architecture (§4.1).  This module closes the loop for the
modern model zoo that already lives in-repo: for each assigned
architecture it builds a small forward callable from the real model code
(`repro.models.lm` / `repro.models.encdec`, which compose
`repro.models.layers`), captures it abstractly with
`frontend.trace.trace_to_graph` (ShapeDtypeStruct parameters — nothing is
allocated, so 32B-parameter architectures trace in seconds on CPU), and
exposes the result under `<arch>:<variant>` names that
`repro.core.apps.build_app` resolves:

    variant "prefill" — full-sequence forward at `PREFILL_SEQ` tokens with
                        `last_only` logits (serving prefill); attention is
                        its two batched matmuls.
    variant "decode"  — one-token decode step against a `DECODE_CACHE`-
                        slot KV cache; the cache tensors are activation
                        vertices, so the Fig. 5 liveness profile (and the
                        Eq. 13 buffer floor) sees KV-cache residency, and
                        the single-row GEMMs lower to `Op.matvec`.

MoE experts are costed at the load the routing gives them: the dropless
`moe_block` runs its expert GEMMs as one grouped `ragged_dot` over the
T x top_k routed rows, which `frontend.lower` costs as balanced routing,
min(E, T*k) expert instances of ceil(T*k / E) rows.  A decode step is
top_k matvec instances (only the routed experts' weights stream), a
128-token prefill over 64 experts at top-6 is 64 instances of 12 rows.

A linear-attention layer's decode state is fixed in size (kimi-linear's
KDA: a [heads, dk, dv] matrix and the short conv's last 3 inputs per
layer); it is an activation vertex of the decode step like a KV cache, so
the Eq. 13 floor sees it.

Graphs are memoized per process; listing `ZOO_APP_NAMES` costs nothing.
Tracing an app records a `frontend.trace` span (args `app`, `ops`,
`columns`: its unique op columns), for MoE models the counter
`frontend.expert_instances`, and for a decode app with recurrent state the
counter `frontend.state_bits` (its bits at the traced width: every
decoder cache entry without a "kv_seq" axis) while `repro.obs` is
enabled.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import ARCH_NAMES, get_arch
from repro.core.graph import ComputationGraph
from repro.frontend.trace import DEFAULT_BIT_WIDTH, trace_to_graph
from repro.models.layers import Runtime, Spec, spec_shapes

__all__ = ["ZOO_APP_NAMES", "ZOO_VARIANTS", "build_zoo_app",
           "PREFILL_SEQ", "DECODE_CACHE"]

# Workload shapes: small enough that the Eq. 11/13 buffer floors stay
# feasible at the default area budget, large enough that prefill is
# matmul-shaped and decode is matvec-shaped.
PREFILL_SEQ = 128
ENCODER_SEQ = 256          # audio-family encoder frames (whisper)
DECODE_CACHE = 128         # KV-cache slots resident during a decode step

ZOO_VARIANTS: Tuple[str, ...] = ("prefill", "decode")

ZOO_APP_NAMES: Tuple[str, ...] = tuple(
    f"{arch}:{variant}" for arch in ARCH_NAMES for variant in ZOO_VARIANTS)


def _sds(shape, dtype=jnp.int32) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _prefill_graph(arch_name: str) -> ComputationGraph:
    arch = get_arch(arch_name)
    rt = Runtime()
    name = f"{arch_name}:prefill"
    tokens = _sds((1, PREFILL_SEQ))
    if arch.is_encdec:
        from repro.models.encdec import EncDecLM
        model = EncDecLM(arch)
        frames = _sds((1, min(arch.encoder_seq, ENCODER_SEQ), arch.d_model),
                      jnp.float32)

        def fn(params, toks, frm):
            return model.forward(params, {"tokens": toks, "frames": frm},
                                 rt, last_only=True)

        return trace_to_graph(fn, spec_shapes(model.param_specs()), tokens,
                              frames, name=name)

    from repro.models.lm import DecoderLM
    model = DecoderLM(arch)

    def fn(params, toks):
        return model.forward(params, {"tokens": toks}, rt, last_only=True)

    return trace_to_graph(fn, spec_shapes(model.param_specs()), tokens,
                          name=name)


def _decode_graph(arch_name: str) -> ComputationGraph:
    arch = get_arch(arch_name)
    rt = Runtime()
    name = f"{arch_name}:decode"
    if arch.is_encdec:
        import dataclasses

        from repro.models.encdec import EncDecLM
        # truncate the decode-time encoder context: the cross-attention KV
        # cache is sized from encoder_seq, and whisper's native 1500 (or
        # even the prefill variant's 256) frames push the Eq. 13 activation
        # floor into a region of the power-of-two buffer lattice whose
        # nearest representable buffer alone exceeds the default area
        # budget
        arch = dataclasses.replace(
            arch, encoder_seq=min(arch.encoder_seq, DECODE_CACHE))
        model = EncDecLM(arch)
    else:
        from repro.models.lm import DecoderLM
        model = DecoderLM(arch)
    specs = model.cache_specs(1, DECODE_CACHE)
    state = 0 if arch.is_encdec else sum(     # (whisper's cross-KV is not)
        math.prod(s.shape) for s in jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, Spec))
        if "kv_seq" not in s.axes)
    if state:
        obs.counter("frontend.state_bits", state * DEFAULT_BIT_WIDTH)
    cache = spec_shapes(specs, jnp.bfloat16)
    token = _sds((1, 1))
    pos = _sds(())

    def fn(params, c, t, p):
        # return the new caches too: their liveness is the decode story
        return model.decode_step(params, c, t, p, rt)

    return trace_to_graph(fn, spec_shapes(model.param_specs()), cache,
                          token, pos, name=name)


_VARIANT_BUILDERS: Dict[str, Callable[[str], ComputationGraph]] = {
    "prefill": _prefill_graph,
    "decode": _decode_graph,
}


@functools.lru_cache(maxsize=None)
def build_zoo_app(name: str) -> ComputationGraph:
    """`"<arch>:<variant>"` -> traced `ComputationGraph` (memoized)."""
    if ":" not in name:
        raise KeyError(f"zoo app names look like 'qwen2-0.5b:prefill'; "
                       f"got {name!r}")
    arch_name, _, variant = name.partition(":")
    if arch_name not in ARCH_NAMES:
        raise KeyError(f"unknown architecture {arch_name!r}; "
                       f"available: {sorted(ARCH_NAMES)}")
    builder = _VARIANT_BUILDERS.get(variant)
    if builder is None:
        raise KeyError(f"unknown variant {variant!r}; "
                       f"available: {sorted(_VARIANT_BUILDERS)}")
    with obs.span("frontend.trace", app=name) as args:
        graph = builder(arch_name)
        if args is not None:
            stream = graph.op_stream()
            args.update(ops=len(stream),
                        columns=len(stream.dedup_columns()[0]))
    return graph
