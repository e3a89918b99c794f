"""Graph capture: JAX callable -> `ComputationGraph` via `jax.make_jaxpr`.

This is the reproduction's analogue of the paper's frozen-graph parser
(§4.1): instead of a TF protobuf, the *target application* is any JAX
callable.  `trace_to_graph` captures its jaxpr abstractly (ShapeDtypeStruct
arguments — no parameters are ever materialized, so 30B-parameter
architectures trace in seconds on CPU), walks every equation including the
closed-over sub-jaxprs of ``pjit`` / ``scan`` / ``remat`` /
``custom_jvp_call`` / ``cond``, and rebuilds the data-dependency DAG the
dynamic-memory analysis of Fig. 5 needs:

  * compute primitives (see `frontend.lower`) become `Op` vertices carrying
    the Table-1 loop bounds plus the actual parameter bits;
  * parameters (the `weight_argnums` pytrees and closed-over constants)
    never become activation vertices — their bits attach to the consuming
    compute op, exactly as the hand-built graphs in `core/apps.py` do;
  * structural data movement (concat, reductions, gathers, cache updates)
    becomes data-only vertices, so tensor liveness — including decode-time
    KV caches — shows up in the Fig. 5 profile;
  * shape/size-preserving unary ops (casts, reshapes, transposes,
    activation functions) are *aliased* onto their producer: they are fused
    in any real pipeline and would otherwise double-count every tensor in
    the liveness analysis.

``scan`` bodies are unrolled (up to `scan_unroll_limit` iterations) so the
per-layer structure of scan-over-layers models is recovered with true
per-iteration liveness.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core.graph import ComputationGraph
from repro.frontend.lower import LOWERING_RULES, OperandInfo, lower_eqn

__all__ = ["trace_to_graph", "trace_jaxpr", "GraphTracer",
           "DEFAULT_BIT_WIDTH"]

# The DSE datapath is quantized (§5: 8-bit dynamic-precision, cf. [7]);
# traced tensors are costed at this width regardless of their jnp dtype,
# matching the BITS=8 convention of the hand-built graphs.
DEFAULT_BIT_WIDTH = 8

# call primitives (what `jax.jit` inside a traced function emits): the
# sub-jaxpr is inlined 1:1.
_CALL_PRIMS = ("jit",)
_REMAT_PRIMS = ("remat2", "remat", "checkpoint")
_CUSTOM_PRIMS = ("custom_jvp_call", "custom_vjp_call",
                 "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr")


@dataclasses.dataclass
class _Binding:
    """What the tracer knows about one jaxpr variable.

    node         — activation vertex name in the graph (None if untracked)
    is_weight    — parameter / closed-over constant (never an activation)
    elems        — abstract element count (alias decisions)
    pending_bits — unclaimed parameter bits: the *first* consumer of a
                   weight claims them onto its graph vertex, so every
                   parameter counts exactly once in `total_weight_bits`
                   even when it reaches the graph through a non-lowered
                   primitive (embedding gathers, bias adds) or is reused
                   (tied embeddings)
    """

    node: Optional[str] = None
    is_weight: bool = False
    elems: int = 0
    pending_bits: int = 0


def _n_elems(aval) -> int:
    shape = getattr(aval, "shape", ())
    out = 1
    for d in shape:
        out *= int(d)
    return out


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")        # jax.core.Literal, version-proof


def _closed(j):
    """(inner_jaxpr, consts) for either a ClosedJaxpr or a plain Jaxpr."""
    if hasattr(j, "jaxpr"):
        return j.jaxpr, list(j.consts)
    return j, []


class GraphTracer:
    """Stateful jaxpr -> ComputationGraph walker."""

    def __init__(self, name: str = "traced",
                 bit_width: int = DEFAULT_BIT_WIDTH,
                 scan_unroll_limit: int = 512):
        self.graph = ComputationGraph()
        self.prefix = name
        self.bw = bit_width
        self.scan_unroll_limit = scan_unroll_limit
        self._n = 0

    # ----------------------------------------------------------- bookkeeping
    def _fresh(self, tag: str) -> str:
        self._n += 1
        return f"{self.prefix}/{tag}_{self._n}"

    def _read(self, env: Dict, atom) -> _Binding:
        if _is_literal(atom):
            return _Binding(elems=_n_elems(getattr(atom, "aval", None)))
        return env.get(atom, _Binding())

    def _data_node(self, tag: str, elems: int, parents: Sequence[str],
                   weight_bits: int = 0) -> str:
        return self.graph.add(self._fresh(tag), None, elems * self.bw,
                              weight_bits, parents=list(parents))

    def _weight_binding(self, elems: int) -> _Binding:
        return _Binding(None, True, elems, pending_bits=elems * self.bw)

    @staticmethod
    def _claim_weights(bindings: Sequence[_Binding]) -> int:
        """Take the unclaimed parameter bits of the weight operands (each
        weight counts once, at its first consumer)."""
        total = 0
        for b in bindings:
            if b.is_weight and b.pending_bits:
                total += b.pending_bits
                b.pending_bits = 0
        return total

    @staticmethod
    def _act_parents(bindings: Sequence[_Binding]) -> List[str]:
        out: List[str] = []
        for b in bindings:
            if b.node is not None and b.node not in out:
                out.append(b.node)
        return out

    # -------------------------------------------------------------- the walk
    def walk(self, jaxpr, env: Dict) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in _CALL_PRIMS:
                self._eval_call(eqn, env, eqn.params["jaxpr"])
            elif prim in _REMAT_PRIMS:
                self._eval_call(eqn, env, eqn.params["jaxpr"])
            elif prim in _CUSTOM_PRIMS:
                inner = eqn.params.get("call_jaxpr",
                                       eqn.params.get("fun_jaxpr"))
                if inner is not None:
                    self._eval_call(eqn, env, inner)
                else:                   # unknown layout: degrade to data
                    self._eval_data(eqn, env)
            elif prim == "scan":
                self._eval_scan(eqn, env)
            elif prim == "cond":
                self._eval_cond(eqn, env)
            else:
                lowered = None
                bindings = [self._read(env, a) for a in eqn.invars]
                # weight-only compute (e.g. a parameter-merge GEMM that a
                # serving stack folds at load time) stays in weight-land —
                # _eval_data classifies the product as a weight, so it is
                # neither costed per inference nor tracked as an activation
                any_act = any(b.node is not None for b in bindings)
                any_weight = any(b.is_weight for b in bindings)
                if prim in LOWERING_RULES and (any_act or not any_weight):
                    operands = [
                        OperandInfo(
                            shape=tuple(getattr(a.aval, "shape", ())),
                            elems=_n_elems(getattr(a, "aval", None)),
                            is_weight=b.is_weight,
                            is_activation=b.node is not None)
                        for a, b in zip(eqn.invars, bindings)
                    ]
                    lowered = lower_eqn(eqn, operands, self._fresh, self.bw)
                if lowered is not None:
                    parents = self._act_parents(bindings)
                    out = eqn.outvars[0]
                    # node weight bits come from the claim, not the operand
                    # shape: a reused parameter (tied embeddings) counts at
                    # its first consumer only
                    w_bits = self._claim_weights(bindings)
                    node = self.graph.add(lowered.op.name, lowered.op,
                                          _n_elems(out.aval) * self.bw,
                                          w_bits, parents)
                    env[out] = _Binding(node, False, _n_elems(out.aval))
                    for extra in eqn.outvars[1:]:
                        env[extra] = _Binding(node, False,
                                              _n_elems(extra.aval))
                else:
                    self._eval_data(eqn, env, bindings)

    # ----------------------------------------------------- default data path
    def _eval_data(self, eqn, env: Dict,
                   bindings: Optional[List[_Binding]] = None) -> None:
        if bindings is None:
            bindings = [self._read(env, a) for a in eqn.invars]
        parents = self._act_parents(bindings)
        # parameter-only computation (casts/transposes/slices of weights)
        # stays in weight-land: no activation vertex, no liveness impact;
        # unclaimed bits flow through to the transformed parameter.
        if not parents and any(b.is_weight for b in bindings):
            pending = self._claim_weights(bindings)
            for i, ov in enumerate(eqn.outvars):
                b = _Binding(None, True, _n_elems(ov.aval))
                if i == 0:
                    b.pending_bits = pending
                env[ov] = b
            return
        # shape/size-preserving unary op on one activation: alias (fused);
        # any weight operand (a norm scale, a bias) counts on the producer.
        if (len(eqn.outvars) == 1 and len(parents) == 1):
            out_elems = _n_elems(eqn.outvars[0].aval)
            src = next(b for b in bindings if b.node == parents[0])
            if out_elems == src.elems:
                claimed = self._claim_weights(bindings)
                if claimed:
                    self.graph.nodes[parents[0]].weight_bits += claimed
                env[eqn.outvars[0]] = _Binding(parents[0], False, out_elems)
                return
        tag = eqn.primitive.name.replace("_", "")[:12] or "data"
        w_bits = self._claim_weights(bindings)
        for ov in eqn.outvars:
            elems = _n_elems(ov.aval)
            node = self._data_node(tag, elems, parents, w_bits)
            w_bits = 0                  # attach once (first output node)
            env[ov] = _Binding(node, False, elems)

    # ----------------------------------------------------- structured prims
    def _eval_call(self, eqn, env: Dict, inner_jaxpr) -> None:
        inner, consts = _closed(inner_jaxpr)
        sub_env: Dict = {}
        for cv, c in zip(inner.constvars, consts):
            sub_env[cv] = self._weight_binding(_n_elems(c))
        for iv, outer in zip(inner.invars, eqn.invars):
            sub_env[iv] = self._read(env, outer)
        self.walk(inner, sub_env)
        for ov, inner_ov in zip(eqn.outvars, inner.outvars):
            env[ov] = self._read(sub_env, inner_ov)

    def _eval_scan(self, eqn, env: Dict) -> None:
        p = eqn.params
        inner, consts = _closed(p["jaxpr"])
        nc, nk = int(p["num_consts"]), int(p["num_carry"])
        length = int(p["length"])
        const_bs = [self._read(env, a) for a in eqn.invars[:nc]]
        carry = [self._read(env, a) for a in eqn.invars[nc:nc + nk]]
        xs = [(a, self._read(env, a)) for a in eqn.invars[nc + nk:]]
        n_ys = len(inner.outvars) - nk
        ys_parents: List[List[str]] = [[] for _ in range(n_ys)]

        steps = min(length, self.scan_unroll_limit)
        if steps < length:
            # no silent caps: a truncated unroll understates MACs, weights,
            # and the Fig. 5 liveness of everything past the limit
            warnings.warn(
                f"{self.prefix}: scan of length {length} unrolled only "
                f"{steps} iterations (scan_unroll_limit="
                f"{self.scan_unroll_limit}); costs are understated — raise "
                f"the limit to cover the full loop", stacklevel=2)
        for _t in range(steps):
            sub_env: Dict = {}
            for cv, c in zip(inner.constvars, consts):
                sub_env[cv] = self._weight_binding(_n_elems(c))
            n_cc = len(const_bs) + len(carry)
            for iv, b in zip(inner.invars[:n_cc], const_bs + carry):
                sub_env[iv] = b
            for iv, (atom, b) in zip(inner.invars[n_cc:], xs):
                elems = max(1, b.elems // max(length, 1))
                if b.node is None:          # weight (stacked params) slice:
                    sub_env[iv] = _Binding(  # each step owns its share
                        None, b.is_weight, elems,
                        pending_bits=b.pending_bits // max(length, 1))
                else:                       # activation xs: per-step slice
                    node = self._data_node("xslice", elems, [b.node])
                    sub_env[iv] = _Binding(node, False, elems)
            self.walk(inner, sub_env)
            carry = [self._read(sub_env, ov) for ov in inner.outvars[:nk]]
            for j, ov in enumerate(inner.outvars[nk:]):
                b = self._read(sub_env, ov)
                if b.node is not None and b.node not in ys_parents[j]:
                    ys_parents[j].append(b.node)

        for ov, b in zip(eqn.outvars[:nk], carry):
            env[ov] = b
        for j, ov in enumerate(eqn.outvars[nk:]):
            elems = _n_elems(ov.aval)
            if ys_parents[j]:
                node = self._data_node("stack", elems, ys_parents[j])
                env[ov] = _Binding(node, False, elems)
            else:
                env[ov] = _Binding(None, False, elems)

    def _eval_cond(self, eqn, env: Dict) -> None:
        """Cost the largest branch (by equation count): the cost model
        wants one representative path (§4.1), and a data-dependent guard's
        cheap/identity branch must not hide the heavy one."""
        branches = eqn.params["branches"]
        sizes = [len(_closed(br)[0].eqns) for br in branches]
        pick = max(range(len(branches)), key=lambda i: sizes[i])
        if len(set(sizes)) > 1:
            warnings.warn(
                f"{self.prefix}: cond with branches of differing size "
                f"{sizes}; only branch {pick} (the largest) is costed",
                stacklevel=2)
        inner, consts = _closed(branches[pick])
        sub_env: Dict = {}
        for cv, c in zip(inner.constvars, consts):
            sub_env[cv] = self._weight_binding(_n_elems(c))
        for iv, outer in zip(inner.invars, eqn.invars[1:]):
            sub_env[iv] = self._read(env, outer)
        self.walk(inner, sub_env)
        for ov, inner_ov in zip(eqn.outvars, inner.outvars):
            env[ov] = self._read(sub_env, inner_ov)


# ---------------------------------------------------------------- front door

def trace_jaxpr(closed_jaxpr, arg_is_weight: Sequence[bool],
                name: str = "traced",
                bit_width: int = DEFAULT_BIT_WIDTH,
                scan_unroll_limit: int = 512) -> ComputationGraph:
    """Lower an already-captured ClosedJaxpr to a `ComputationGraph`.

    `arg_is_weight[i]` classifies the i-th flat invar as a parameter.
    """
    jaxpr = closed_jaxpr.jaxpr
    if len(arg_is_weight) != len(jaxpr.invars):
        raise ValueError(
            f"classification covers {len(arg_is_weight)} invars, jaxpr has "
            f"{len(jaxpr.invars)}")
    tracer = GraphTracer(name, bit_width, scan_unroll_limit)
    env: Dict = {}
    n_in = 0
    for var, is_w in zip(jaxpr.invars, arg_is_weight):
        elems = _n_elems(var.aval)
        if is_w:
            env[var] = tracer._weight_binding(elems)
        else:
            n_in += 1
            node = tracer.graph.add(f"{name}/input_{n_in}", None,
                                    elems * bit_width)
            env[var] = _Binding(node, False, elems)
    for cv, c in zip(jaxpr.constvars, closed_jaxpr.consts):
        env[cv] = tracer._weight_binding(_n_elems(c))
    tracer.walk(jaxpr, env)
    return tracer.graph


def trace_to_graph(fn, *args, name: str = "traced",
                   weight_argnums: Tuple[int, ...] = (0,),
                   bit_width: int = DEFAULT_BIT_WIDTH,
                   scan_unroll_limit: int = 512) -> ComputationGraph:
    """Capture `fn(*args)` and lower it to the canonical graph IR.

    `args` may be real arrays or `jax.ShapeDtypeStruct`s (abstract tracing
    — nothing is allocated).  The pytrees at `weight_argnums` are treated
    as model parameters: their leaves attach to consuming compute ops as
    weight bits instead of becoming activation vertices.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    arg_is_weight: List[bool] = []
    for i, a in enumerate(args):
        arg_is_weight.extend([i in weight_argnums] * len(jax.tree.leaves(a)))
    return trace_jaxpr(closed, arg_is_weight, name=name,
                       bit_width=bit_width,
                       scan_unroll_limit=scan_unroll_limit)
