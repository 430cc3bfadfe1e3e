"""Epilogue-fusion pass: realize the cost model's ranked candidates.

``cost._fusion_candidates`` has ranked maximal single-consumer chains
by HBM traffic saved since PR 6 — "the MPK-style feed for the Pallas
tier" — but nothing consumed them.  This pass is the consumer: it walks
the candidates of a recorded Program, pattern-matches each chain's
prefix against the epilogue recipes ``ops.pallas.fused_epilogue``
implements (linear anchor + bias/gelu/relu/residual-add/layer_norm
stages), checks the kernel's shape/dtype gate against the *run-time*
avals, and hands the static Executor a rewrite plan: the matched nodes
collapse into ONE node calling the fused Pallas kernel (fwd +
custom-vjp bwd), so the candidate's ``saved_bytes`` become real HBM
savings instead of a report line.  The analog of the reference's
``ir/*_fuse_pass.cc`` chain matchers feeding ``operators/fused/``.

Two consumers, one matcher — so prediction and execution can never
disagree about what fuses:

- ``Executor._build`` calls :func:`plan_fusions` + :func:`apply_plans`
  to rewrite the node list before tracing (gated on the Pallas tier
  being active, single-device plans only);
- ``Program.analyze`` calls :func:`annotate_candidates` to mark each
  reported candidate ``realized`` (with the kernel label) or not,
  so the report distinguishes realized from still-unrealized savings.

Matching is conservative by contract: a chain the matcher cannot prove
safe (unreadable closure, unexpected kwargs, gate miss) is left on the
composite path untouched.  Errors in the analysis itself propagate.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from ..program import _OpNode

__all__ = ["plan_fusions", "apply_plans", "annotate_candidates",
           "FusionPlan"]

_MISS = object()


def _free(fn, name, default=_MISS):
    """Read a closure freevar off a recorded op fn (the lint/transform
    layers already rely on these recording closures being plain Python
    functions); ``default`` when absent/unreadable."""
    code = getattr(fn, "__code__", None)
    cells = getattr(fn, "__closure__", None)
    if code is None or cells is None:
        return default
    try:
        return cells[code.co_freevars.index(name)].cell_contents
    except (ValueError, IndexError):  # not a freevar of this fn
        return default


class FusionPlan:
    """One matched chain prefix -> one fused-kernel node."""

    __slots__ = ("node_indices", "stages", "x_spec", "w_spec", "b_spec",
                 "operand_specs", "out_var", "label")

    def __init__(self, node_indices, stages, x_spec, w_spec, b_spec,
                 operand_specs, out_var, label):
        self.node_indices = list(node_indices)
        self.stages = tuple(stages)
        self.x_spec = x_spec
        self.w_spec = w_spec
        self.b_spec = b_spec
        self.operand_specs = list(operand_specs)
        self.out_var = out_var
        self.label = label


def _aval_of(spec, avals):
    """Shape/dtype carrier for an in_spec entry."""
    tag, x = spec
    if tag == "v":
        return avals.get(id(x), x.data)
    if tag == "p":
        from .liveness import param_array
        return param_array(x)
    if tag == "c":
        return x
    return None


def _match_chain(nodes, chain, avals) -> Optional[FusionPlan]:
    """Match the longest realizable prefix of one candidate chain."""
    import numpy as np

    from ...ops.pallas.fused_epilogue import (fused_epilogue_supported,
                                              stage_label)

    anchor = nodes[chain[0]]
    if anchor.op_name != "linear" or anchor.kw:
        return None
    if len(anchor.in_specs) not in (2, 3):
        return None
    x_spec, w_spec = anchor.in_specs[0], anchor.in_specs[1]
    b_spec = anchor.in_specs[2] if len(anchor.in_specs) == 3 else None
    w_aval = _aval_of(w_spec, avals)
    x_aval = _aval_of(x_spec, avals)
    if w_aval is None or x_aval is None or len(w_aval.shape) != 2:
        return None
    n = int(w_aval.shape[1])
    out_aval = avals.get(id(anchor.out_vars[0]), anchor.out_vars[0].data)
    out_shape = tuple(int(s) for s in out_aval.shape)

    stages: List[tuple] = []
    operand_specs: List[tuple] = []
    operand_shapes: List[tuple] = []
    fused = [chain[0]]
    chain_var = anchor.out_vars[0]

    for idx in chain[1:]:
        node = nodes[idx]
        name = node.op_name
        st = None
        ops: List[tuple] = []
        if name == "relu" and len(node.in_specs) == 1 and not node.kw:
            st = ("relu",)
        elif name == "gelu" and len(node.in_specs) == 1 and not node.kw:
            approx = _free(node.fn, "approximate")
            if isinstance(approx, bool):
                st = ("gelu", approx)
        elif name == "add" and len(node.in_specs) == 2 and not node.kw:
            other = [s for s in node.in_specs
                     if not (s[0] == "v" and s[1] is chain_var)]
            if len(other) == 1:
                o_aval = _aval_of(other[0], avals)
                if o_aval is not None:
                    shp = tuple(int(s) for s in o_aval.shape)
                    if shp == out_shape or shp == (n,) or shp == (1, n):
                        st = ("add",) if shp == out_shape else ("bias",)
                        ops = [other[0]]
        elif name == "layer_norm" and not node.kw \
                and 1 <= len(node.in_specs) <= 3 \
                and node.in_specs[0][0] == "v" \
                and node.in_specs[0][1] is chain_var:
            ndims = _free(node.fn, "n")
            eps = _free(node.fn, "epsilon")
            if ndims == 1 and isinstance(eps, float):
                affine = node.in_specs[1:]
                good = all(
                    (a := _aval_of(sp, avals)) is not None
                    and tuple(int(s) for s in a.shape) in ((n,), (1, n))
                    for sp in affine)
                if good:
                    has_w = len(affine) >= 1
                    has_b = len(affine) >= 2
                    st = ("layer_norm", eps, has_w, has_b)
                    ops = list(affine)
        if st is None:
            break
        # the chain var must feed this node (candidates guarantee it,
        # but add's operand filter above is identity-based — re-check)
        if not any(s[0] == "v" and s[1] is chain_var
                   for s in node.in_specs):
            break
        stages.append(st)
        operand_specs.extend(ops)
        operand_shapes.extend(
            tuple(int(s) for s in _aval_of(sp, avals).shape)
            for sp in ops)
        fused.append(idx)
        chain_var = node.out_vars[0]
        out_shape = tuple(int(s) for s in avals.get(
            id(chain_var), chain_var.data).shape)

    if len(fused) < 2:
        return None  # a bare matmul saves nothing — not a realization

    # the "bias" stage synthesized from a broadcast add consumes its
    # operand like the anchor bias does; gate sees the full recipe
    gate_stages = ((("bias",),) if b_spec is not None else ()) \
        + tuple(stages)
    gate_ops = ([tuple(int(s) for s in _aval_of(b_spec, avals).shape)]
                if b_spec is not None else []) + operand_shapes
    x_shape = tuple(int(s) for s in x_aval.shape)
    dtype = np.dtype(x_aval.dtype)
    if not fused_epilogue_supported(x_shape, tuple(
            int(s) for s in w_aval.shape), dtype, gate_stages, gate_ops):
        return None
    return FusionPlan(fused, stages, x_spec, w_spec, b_spec,
                      operand_specs, nodes[fused[-1]].out_vars[0],
                      stage_label(gate_stages))


def _candidates(graph, avals, fetched_ids):
    from .cost import _fusion_candidates, _node_costs
    costs = _node_costs(graph, avals)
    return _fusion_candidates(graph, costs, avals, fetched_ids, None)


def plan_fusions(program, fetch_list=None,
                 feed_shapes: Optional[Dict[str, Sequence[int]]] = None
                 ) -> List[FusionPlan]:
    """Match every ranked candidate of ``program`` against the kernel
    recipes under the given concrete feed shapes (run-time avals — the
    recorded placeholder batch of 1 would fail the row-tile gate).
    Returns the realizable plans.  A chain the matcher cannot prove
    safe is skipped (``_match_chain`` returns None); a failure of the
    analysis itself raises — swallowed, it would turn a run that should
    be fused into a silently unfused one."""
    from .cost import _propagate_avals
    from .graph import DefUseGraph
    graph = DefUseGraph(program)
    avals = (_propagate_avals(graph, dict(feed_shapes))
             if feed_shapes else {})
    fetched = set()
    for f in (fetch_list or []):
        v = graph.resolve_fetch(f)
        if v is not None:
            fetched.add(id(v))
    plans = []
    for cand in _candidates(graph, avals, fetched):
        plan = _match_chain(graph.nodes, cand["ops"], avals)
        if plan is not None:
            plans.append(plan)
    return plans


def apply_plans(nodes: Sequence[_OpNode], plans: Sequence[FusionPlan]
                ) -> List[_OpNode]:
    """Rewrite the node list: each plan's nodes collapse into one fused
    node at the position of the chain's LAST member (every input is
    produced at or before its original position; the dropped
    intermediates have no consumer outside the chain by construction)."""
    from ...ops.pallas.fused_epilogue import fused_linear_epilogue

    drop: Dict[int, FusionPlan] = {}
    last: Dict[int, FusionPlan] = {}
    for p in plans:
        for i in p.node_indices:
            drop[i] = p
        last[p.node_indices[-1]] = p

    out: List[_OpNode] = []
    for i, node in enumerate(nodes):
        p = last.get(i)
        if p is not None:
            has_bias = p.b_spec is not None
            stages = p.stages

            def make_fn(stages=stages, has_bias=has_bias):
                def fused_fn(x, w, *rest):
                    bias = rest[0] if has_bias else None
                    operands = rest[1:] if has_bias else rest
                    return fused_linear_epilogue(
                        x, w, bias, stages, operands)
                return fused_fn

            in_specs = [p.x_spec, p.w_spec]
            if has_bias:
                in_specs.append(p.b_spec)
            in_specs.extend(p.operand_specs)
            out.append(_OpNode(make_fn(), {}, "pallas_fused_epilogue",
                               in_specs, [p.out_var], False,
                               loc=node.loc))
        elif i not in drop:
            out.append(node)
    return out


def annotate_candidates(program, candidates, graph, avals,
                        fetched_ids=(), plan_active=False) -> None:
    """Mark each reported candidate dict with what the executor's pass
    would realize for it right now: ``realized`` (kernel label or
    None) and ``realized_ops`` (the fused prefix).  Gated exactly like
    the executor — tier flags (``ops.pallas.support.tier_enabled``)
    AND no sharding plan (``plan_active``; the executor skips the pass
    under an explicit GSPMD lowering) — so the report states what
    actually happens, not what hypothetically could."""
    from ...ops.pallas.support import tier_enabled
    active = tier_enabled() and not plan_active
    for cand in candidates:
        cand["realized"] = None
        cand["realized_ops"] = []
        if not active:
            continue
        try:
            plan = _match_chain(graph.nodes, cand["ops"], avals)
        except Exception:  # noqa: BLE001 - annotation is best-effort
            plan = None
        if plan is not None:
            cand["realized"] = plan.label
            cand["realized_ops"] = list(plan.node_indices)
