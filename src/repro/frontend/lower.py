"""Lowering rules: jaxpr primitives -> Table-1 operation embeddings.

The paper's frontend parses a frozen TF graph and embeds every
time-consuming operation into the canonical 2-D-convolution coordinates of
Table 1 (§4.1).  Here the same role is played by a registry from jaxpr
primitive name to a lowering rule:

  * ``dot_general``           -> `Op.matmul` (prefill: row block > 1) or
                                 `Op.matvec` (decode: a single activation
                                 row); contraction batch dimensions
                                 (attention heads, MoE experts) become
                                 `repeat` instances via
                                 `Op.batched_matmul`/`Op.batched_matvec`.
  * ``ragged_dot_general``    -> the dropless grouped expert GEMM
                                 (`jax.lax.ragged_dot`): M routed rows
                                 over G experts cost as balanced routing,
                                 min(G, M) instances of ceil(M / min(G, M))
                                 rows, so only the experts the routing
                                 touches are streamed.
  * ``conv_general_dilated``  -> `Op` CONV2D / CHANNEL_MIXING (1x1) /
                                 DEPTHWISE_CONV (feature-group dispatch,
                                 grouped convs as `repeat`ed per-group
                                 convs).
  * everything else           -> no rule: the tracer records a data-only
                                 node (or aliases shape/dtype-preserving
                                 ops), so the Fig. 5 liveness analysis sees
                                 the dependency structure while the cost
                                 model only ever sees compute ops ("We only
                                 focus on the time-consuming operations",
                                 §4.1).

A rule receives the eqn, the operand descriptors (`OperandInfo`: shape,
element count, weight/activation classification) and a fresh-name factory;
it returns a `Lowered` record (the embedded `Op`) or ``None`` to fall back
to data-only handling.  The *parameter bits* of the resulting graph vertex
are attached by the tracer's claim mechanism (each weight counts once, at
its first consumer), not by the rule.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro import obs
from repro.core.costmodel import Op, OpKind

__all__ = ["Lowered", "OperandInfo", "LOWERING_RULES", "register_lowering",
           "lower_eqn"]


@dataclasses.dataclass(frozen=True)
class OperandInfo:
    """What a lowering rule may know about one eqn operand."""

    shape: Tuple[int, ...]
    elems: int
    is_weight: bool        # parameter / closed-over constant
    is_activation: bool    # tracked activation node exists for it


@dataclasses.dataclass(frozen=True)
class Lowered:
    """One costable operation produced by a lowering rule."""

    op: Op


LoweringRule = Callable[..., Optional[Lowered]]

LOWERING_RULES: Dict[str, LoweringRule] = {}


def register_lowering(prim_name: str):
    """Decorator: install a rule for `prim_name` (last registration wins,
    so downstream code can override the built-in embeddings)."""

    def deco(fn: LoweringRule) -> LoweringRule:
        LOWERING_RULES[prim_name] = fn
        return fn

    return deco


def lower_eqn(eqn, operands: Sequence[OperandInfo], fresh_name, bit_width):
    """Dispatch `eqn` through the registry; None when no rule applies."""
    rule = LOWERING_RULES.get(eqn.primitive.name)
    if rule is None:
        return None
    return rule(eqn, operands, fresh_name, bit_width)


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


# ------------------------------------------------------------- dot_general

@register_lowering("dot_general")
def _lower_dot_general(eqn, operands, fresh_name, bit_width):
    """General contraction -> Table 1 rows 4/5.

    The free dimensions of the *activation* operand become the row block
    (`row1`); the free dimensions of the *weight* operand the column block
    (`col2`); contracted dimensions multiply into `col1`.  Batch dimensions
    index independent instances (per-head attention matmuls, per-expert
    GEMMs) and map to `repeat`.  A single activation row (decode-time
    token, or an FC layer at batch 1) is the matrix-vector special case.
    """
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = operands[0], operands[1]
    k = _prod(lhs.shape[i] for i in lc)
    inst = _prod(lhs.shape[i] for i in lb)
    lhs_free = _prod(d for i, d in enumerate(lhs.shape)
                     if i not in lc and i not in lb)
    rhs_free = _prod(d for i, d in enumerate(rhs.shape)
                     if i not in rc and i not in rb)

    if lhs.is_weight and not rhs.is_weight:
        # W @ x orientation: activation supplies the rows
        m, n = rhs_free, lhs_free
    else:
        m, n = lhs_free, rhs_free

    if min(m, n) == 1:
        op = Op.batched_matvec(col=k, row=max(m, n), instances=inst,
                               name=fresh_name("matvec"))
    else:
        op = Op.batched_matmul(col1=k, row1=m, col2=n, instances=inst,
                               name=fresh_name("matmul"))
    return Lowered(op=op)


# ------------------------------------------------------ ragged_dot_general

@register_lowering("ragged_dot_general")
def _lower_ragged_dot(eqn, operands, fresh_name, bit_width):
    """Grouped GEMM [M, K] x [G, K, N] (rows sorted by group) -> Table 1
    rows 4/5 at the load balanced routing gives each group.

    The group sizes are data, unknown to an abstract trace; the rows are
    spread evenly over min(G, M) groups, each an instance of
    ceil(M / min(G, M)) rows.  Fewer rows than groups (a decode step's
    top-k slots) is `Op.batched_matvec`, one instance per routed row.
    Counts the instances as `frontend.expert_instances`.  Only the
    forward form (the ragged dimension is a row dimension of the
    activation) is costed.
    """
    rdn = eqn.params["ragged_dot_dimension_numbers"]
    (lc, rc), (lb, rb) = rdn.dot_dimension_numbers
    lhs, rhs = operands[0], operands[1]
    if any(d in lc or d in lb for d in rdn.lhs_ragged_dimensions):
        return None
    groups = _prod(rhs.shape[i] for i in rdn.rhs_group_dimensions)
    k = _prod(lhs.shape[i] for i in lc)
    rows = _prod(d for i, d in enumerate(lhs.shape)
                 if i not in lc and i not in lb)
    n = _prod(d for i, d in enumerate(rhs.shape)
              if i not in rc and i not in rb
              and i not in rdn.rhs_group_dimensions)
    inst = min(groups, rows) * _prod(lhs.shape[i] for i in lb)
    per_group = -(-rows // min(groups, rows))
    if per_group == 1:
        op = Op.batched_matvec(col=k, row=n, instances=inst,
                               name=fresh_name("expert_matvec"))
    else:
        op = Op.batched_matmul(col1=k, row1=per_group, col2=n,
                               instances=inst,
                               name=fresh_name("expert_matmul"))
    obs.counter("frontend.expert_instances", inst)
    return Lowered(op=op)


# ---------------------------------------------------- conv_general_dilated

@register_lowering("conv_general_dilated")
def _lower_conv(eqn, operands, fresh_name, bit_width):
    """2-D convolution family -> Table 1 rows 1-3 (feature-group dispatch).

    feature_group_count == Nif with a single filter per channel is the
    depthwise embedding (Nof = 1, repeat = channels); other grouped convs
    cost one per-group conv repeated `groups` times; 1x1 kernels are
    channel mixing.
    """
    dn = eqn.params["dimension_numbers"]
    strides = tuple(eqn.params["window_strides"])
    groups = int(eqn.params.get("feature_group_count", 1))
    lhs, rhs = operands[0], operands[1]

    batch = int(lhs.shape[dn.lhs_spec[0]])
    cin = int(lhs.shape[dn.lhs_spec[1]])
    spatial_in = [int(lhs.shape[i]) for i in dn.lhs_spec[2:]]
    cout = int(rhs.shape[dn.rhs_spec[0]])
    kernel = [int(rhs.shape[i]) for i in dn.rhs_spec[2:]]
    out_shape = eqn.outvars[0].aval.shape
    spatial_out = [int(out_shape[i]) for i in dn.out_spec[2:]]

    def dim2(xs: List[int]) -> Tuple[int, int]:
        return (xs[0], xs[1]) if len(xs) >= 2 else (xs[0], 1)

    nix, niy = dim2(spatial_in)
    nkx, nky = dim2(kernel)
    nox, noy = dim2(spatial_out)
    s = int(strides[0]) if strides else 1

    if groups == cin and cout == cin:
        op = Op(OpKind.DEPTHWISE_CONV, 1, nix, niy, nkx, nky, 1, nox, noy,
                s, batch, fresh_name("dwconv"), repeat=cin)
    elif groups > 1:
        op = Op(OpKind.CONV2D, cin // groups, nix, niy, nkx, nky,
                cout // groups, nox, noy, s, batch,
                fresh_name("groupconv"), repeat=groups)
    elif nkx == 1 and nky == 1:
        op = Op(OpKind.CHANNEL_MIXING, cin, nix, niy, 1, 1, cout, nox, noy,
                s, batch, fresh_name("chmix"))
    else:
        op = Op(OpKind.CONV2D, cin, nix, niy, nkx, nky, cout, nox, noy,
                s, batch, fresh_name("conv"))
    return Lowered(op=op)
