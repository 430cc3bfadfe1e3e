"""Static cost model: per-op FLOPs/bytes, roofline, fusion candidates.

Quantitative sibling of the verifier passes: everything here is
computed from the *recorded avals* of the op list — no execution, no
profiler.  The outputs are the facts the remaining ROADMAP items
consume: the Pallas mega-kernel tier (ROADMAP 4) picks fusion
candidates by per-chain memory-traffic savings (the MPK selection
criterion), and the sharding engine (ROADMAP 1) needs per-op byte
volumes to price resharding.

Honesty contract: every op lands in exactly one of *modeled* (a rule in
the table below priced it) or the explicit ``unmodeled`` bucket, whose
op count and byte volume ride every total — a report never silently
undercounts because an op had no rule.

Entry points:

- :func:`analyze` / ``Program.analyze(...)`` -> :class:`ProgramReport`
  (per-op table, totals, liveness memory, roofline, hazards, top-k
  fusion candidates);
- :func:`compile_summary` — the light always-on slice the static
  Executor attaches to every compile via
  ``observability.record_compile`` (predicted FLOPs/peak bytes next to
  the attribution record, so predicted-vs-measured drift is visible);
- :data:`CHIP_SPECS` — default roofline specs (cpu / v4 / v5e / v5p).
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..program import Program, Variable
from .graph import DefUseGraph
from .liveness import MemoryEstimate, aval_bytes, estimate_memory
from .passes import Diagnostic

__all__ = ["ChipSpec", "CHIP_SPECS", "OpCost", "ProgramReport",
           "analyze", "compile_summary"]


# ---------------------------------------------------------------------------
# chip specs (public peak numbers; bf16/fp32-mixed systolic peak, HBM BW)
# ---------------------------------------------------------------------------

class ChipSpec:
    """Roofline corner of one accelerator.  ``ici_bw`` is the nominal
    per-chip interconnect bandwidth (bytes/s through one device's
    links) that turns the grad-comm plan's wire bytes into seconds —
    the exposed-comm model divides per-bucket wire bytes by it."""

    __slots__ = ("name", "peak_flops", "hbm_bw", "hbm_bytes", "ici_bw")

    def __init__(self, name: str, peak_flops: float, hbm_bw: float,
                 hbm_bytes: int, ici_bw: float = 0.0):
        self.name = name
        self.peak_flops = float(peak_flops)
        self.hbm_bw = float(hbm_bw)
        self.hbm_bytes = int(hbm_bytes)
        self.ici_bw = float(ici_bw)

    def to_dict(self) -> dict:
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_bw": self.hbm_bw, "hbm_bytes": self.hbm_bytes,
                "ici_bw": self.ici_bw}


CHIP_SPECS: Dict[str, ChipSpec] = {
    # nominal host CPU: AVX-512-ish core complex + DDR5 channel pair;
    # 'interconnect' between virtual devices is a memcpy
    "cpu": ChipSpec("cpu", 200e9, 40e9, 16 << 30, 20e9),
    "v4": ChipSpec("v4", 275e12, 1228e9, 32 << 30, 300e9),
    "v5e": ChipSpec("v5e", 197e12, 819e9, 16 << 30, 186e9),
    "v5p": ChipSpec("v5p", 459e12, 2765e9, 95 << 30, 600e9),
}


# ---------------------------------------------------------------------------
# per-op FLOP rules
# ---------------------------------------------------------------------------

def _numel(aval) -> int:
    n = 1
    for s in aval.shape:
        n *= int(s)
    return n


# elementwise ops: flops = factor * output elements
_ELEMENTWISE: Dict[str, int] = {
    "add": 1, "subtract": 1, "multiply": 1, "divide": 1, "pow": 1,
    "scale": 2, "clip": 2, "abs": 1, "negative": 1, "sign": 1,
    "maximum": 1, "minimum": 1, "floor": 1, "ceil": 1, "round": 1,
    "square": 1, "reciprocal": 1, "remainder": 1, "floor_divide": 1,
    "equal": 1, "not_equal": 1, "greater_than": 1, "greater_equal": 1,
    "less_than": 1, "less_equal": 1, "logical_and": 1, "logical_or": 1,
    "logical_not": 1, "logical_xor": 1, "bitwise_not": 1, "where": 1,
    "isnan": 1, "isinf": 1, "isfinite": 1, "isclose": 4, "add_n": 1,
    "relu": 1, "relu6": 2, "leaky_relu": 2, "prelu": 2, "hardtanh": 2,
    "hardshrink": 2, "softshrink": 2, "thresholded_relu": 2,
    "hardsigmoid": 3, "maxout": 2, "masked_fill": 1, "increment": 1,
    "exp": 10, "log": 10, "log2": 10, "log10": 10, "log1p": 10,
    "expm1": 10, "sqrt": 10, "rsqrt": 10, "sin": 10, "cos": 10,
    "tan": 10, "asin": 10, "acos": 10, "atan": 10, "sinh": 10,
    "cosh": 10, "tanh": 10, "asinh": 10, "acosh": 10, "atanh": 10,
    "sigmoid": 10, "log_sigmoid": 12, "softplus": 12, "silu": 11,
    "swish": 11, "gelu": 14, "elu": 11, "selu": 12, "celu": 11,
    "stanh": 11, "mish": 14, "erf": 10, "erfinv": 12,
    "dropout": 3, "alpha_dropout": 4, "label_smooth": 2,
    "lerp": 3, "logaddexp": 12, "nan_to_num": 2, "one_hot": 1,
    "gumbel_softmax": 15, "deg2rad": 1, "rad2deg": 1, "cast": 0,
}

# reductions: flops = factor * input elements
_REDUCE: Dict[str, int] = {
    "sum": 1, "mean": 1, "max": 1, "min": 1, "prod": 1, "all": 1,
    "any": 1, "argmax": 1, "argmin": 1, "count_nonzero": 1,
    "nansum": 2, "nanmean": 2, "norm": 2, "std": 4, "var": 3,
    "logsumexp": 12, "cumsum": 1, "cumprod": 1, "cummax": 1,
    "logcumsumexp": 12, "trace": 1, "median": 8, "kthvalue": 8,
    "mode": 8, "sort": 16, "argsort": 16, "topk": 8, "dist": 3,
    "allclose": 4, "histogram": 2, "bincount": 1, "diff": 1,
    "searchsorted": 8, "pool": None,  # pool priced by its window below
}

# pure data movement / indexing: modeled, zero FLOPs
_MOVEMENT = frozenset({
    "reshape", "flatten", "squeeze", "unsqueeze", "transpose", "t",
    "swapaxes", "moveaxis", "slice", "strided_slice", "split", "unbind",
    "concat", "stack", "tile", "expand", "expand_as", "broadcast_to",
    "broadcast_tensors", "gather", "gather_nd", "index_select",
    "index_sample", "take_along_axis", "put_along_axis", "scatter",
    "scatter_nd_add", "embedding", "pad", "flip", "roll", "rot90",
    "clone", "crop_tensor", "diag", "diag_embed", "diagflat", "tril",
    "triu", "repeat_interleave", "shard_index", "sequence_mask",
    "multiplex", "set_value", "assign", "identity", "numel", "shape",
})

# normalizations: flops = factor * input elements (stats + affine)
_NORMALIZE: Dict[str, int] = {
    "batch_norm": 8, "layer_norm": 8, "instance_norm": 8,
    "group_norm": 8, "local_response_norm": 10, "normalize": 6,
    "spectral_norm": 10, "softmax": 5, "log_softmax": 6,
    "sequence_softmax": 5,
}

# losses: factor * first-input elements
_LOSS: Dict[str, int] = {
    "mse_loss": 4, "l1_loss": 3, "smooth_l1_loss": 5,
    "square_error_cost": 3, "cross_entropy": 8,
    "linear_cross_entropy": 8, "binary_cross_entropy": 12,
    "bce_with_logits": 14, "nll_loss": 3, "kl_div": 12, "log_loss": 12,
    "hinge_embedding_loss": 4, "margin_ranking_loss": 4,
    "cosine_embedding_loss": 8, "ctc_loss": 32, "dice_loss": 6,
    "npair_loss": 8, "sigmoid_focal_loss": 16, "hsigmoid_loss": 10,
}


def _contracted_dim(in_avals, kw) -> int:
    """K of a matmul from the lhs aval, honoring transpose kwargs."""
    a = in_avals[0]
    if not a.shape:
        return 1
    tx = bool(kw.get("transpose_x", kw.get("transpose_a", False)))
    return int(a.shape[-2] if (tx and len(a.shape) >= 2) else a.shape[-1])


class OpCost:
    """One op's modeled cost (or its explicit unmodeled admission)."""

    __slots__ = ("op_index", "op_name", "rule", "flops", "in_bytes",
                 "out_bytes", "param_bytes", "modeled", "loc")

    def __init__(self, op_index, op_name, rule, flops, in_bytes,
                 out_bytes, param_bytes, modeled, loc=None):
        self.op_index = op_index
        self.op_name = op_name
        self.rule = rule
        self.flops = int(flops)
        self.in_bytes = int(in_bytes)
        self.out_bytes = int(out_bytes)
        self.param_bytes = int(param_bytes)
        self.modeled = modeled
        self.loc = loc

    @property
    def total_bytes(self) -> int:
        return self.in_bytes + self.out_bytes + self.param_bytes

    def to_dict(self) -> dict:
        return {s: getattr(self, s) for s in self.__slots__}

    def __repr__(self):
        return (f"OpCost(#{self.op_index} {self.op_name}: "
                f"flops={self.flops}, bytes={self.total_bytes}, "
                f"modeled={self.modeled})")


def _op_flops(node, in_avals, param_avals, out_avals
              ) -> Tuple[Optional[int], str]:
    """(flops, rule name) or (None, 'unmodeled')."""
    name = node.op_name
    out_n = sum(_numel(a) for a in out_avals)
    in_n = _numel(in_avals[0]) if in_avals else 0

    if name in ("linear", "addmm"):
        k = _contracted_dim(in_avals or param_avals, node.kw)
        bias = out_n if (len(param_avals) > 1 or name == "addmm") else 0
        return 2 * out_n * k + bias, "matmul"
    if name in ("matmul", "matmul_transpose", "mm", "bmm", "mv",
                "inner", "outer", "dot"):
        k = (_contracted_dim(in_avals, node.kw) if in_avals else 1)
        if name == "outer":
            k = 1
        return 2 * out_n * k, "matmul"
    if name in ("conv2d", "conv3d", "conv1d", "sequence_conv"):
        # weight [Co, Ci/g, *k]: each output element costs one dot of
        # length Ci/g * prod(kernel)
        if param_avals:
            w = param_avals[0]
            dot = _numel(w) // max(int(w.shape[0]), 1)
            bias = out_n if len(param_avals) > 1 else 0
            return 2 * out_n * dot + bias, "conv"
        return None, "unmodeled"
    if name in ("conv2d_transpose", "conv3d_transpose"):
        # every input element scatters one weight-sized stencil
        if param_avals:
            w = param_avals[0]
            dot = _numel(w) // max(int(w.shape[0]), 1)
            bias = out_n if len(param_avals) > 1 else 0
            return 2 * in_n * dot + bias, "conv"
        return None, "unmodeled"
    if name == "pool":
        win = node.kw.get("window", ())
        wn = 1
        for s in win:
            wn *= int(s)
        return out_n * max(wn, 1), "reduce"
    if name in ("adaptive_avg_pool1d", "adaptive_avg_pool2d",
                "adaptive_avg_pool3d", "adaptive_max_pool1d",
                "adaptive_max_pool2d", "adaptive_max_pool3d",
                "interpolate", "pixel_shuffle", "unfold", "grid_sample",
                "affine_grid", "temporal_shift"):
        return 2 * max(in_n, out_n), "sample"
    if name in ("scaled_dot_product_attention", "flash_attention"):
        # q,k,v avals: 2 * numel(q) * Lk for QK^T plus the same for PV,
        # plus a softmax over the score matrix (approximate)
        if len(in_avals) >= 2 and len(in_avals[1].shape) >= 2:
            q, kv = in_avals[0], in_avals[1]
            lk = int(kv.shape[-2]) if len(kv.shape) >= 2 else 1
            scores = _numel(q) // max(int(q.shape[-1]), 1) * lk
            return 4 * _numel(q) * lk + 5 * scores, "attention"
        return None, "unmodeled"
    if name == "paged_attention":
        # serving decode attention over a page-table-indexed KV pool:
        # q [S, H, D], pool [(L,) N, page, Hkv, D], page_table [S, P].
        # Logical context T = P * page; QK^T + PV cost 4*numel(q)*T,
        # softmax ~5 per score.  (Input bytes are corrected to the
        # page GATHER volume in _node_costs — the op reads S*T rows of
        # K and V, not the whole physical pool.)
        if len(in_avals) >= 4 and len(in_avals[0].shape) == 3 \
                and len(in_avals[3].shape) == 2:
            q, kp, pt = in_avals[0], in_avals[1], in_avals[3]
            page = int(kp.shape[-3])
            T = int(pt.shape[1]) * page
            scores = _numel(q) // max(int(q.shape[-1]), 1) * T
            return 4 * _numel(q) * T + 5 * scores, "attention"
        return None, "unmodeled"
    if name in _NORMALIZE:
        return _NORMALIZE[name] * max(in_n, out_n), "normalize"
    if name in _LOSS:
        return _LOSS[name] * in_n, "loss"
    if name in _ELEMENTWISE:
        return _ELEMENTWISE[name] * out_n, "elementwise"
    if name in _REDUCE:
        return (_REDUCE[name] or 1) * in_n, "reduce"
    if name in _MOVEMENT:
        return 0, "movement"
    return None, "unmodeled"


def _node_costs(graph: DefUseGraph,
                avals: Optional[Dict[int, object]] = None) -> List[OpCost]:
    import jax

    from .liveness import param_array

    avals = avals or {}

    def aval_of(v):
        return avals.get(id(v), v.data)

    out: List[OpCost] = []
    for i, node in enumerate(graph.nodes):
        in_avals, param_avals = [], []
        in_bytes = param_bytes = 0
        for tag, x in node.in_specs:
            if tag == "v":
                a = aval_of(x)
                in_avals.append(a)
                in_bytes += aval_bytes(a)
            elif tag == "p":
                arr = param_array(x)
                a = jax.ShapeDtypeStruct(tuple(arr.shape),
                                         np.dtype(arr.dtype))
                param_avals.append(a)
                param_bytes += aval_bytes(a)
            elif tag == "c":
                in_avals.append(x)
                in_bytes += aval_bytes(x)
            elif isinstance(x, np.ndarray):
                in_avals.append(x)
                in_bytes += aval_bytes(x)
        out_avals = [aval_of(v) for v in node.out_vars]
        out_bytes = sum(aval_bytes(a) for a in out_avals)
        flops, rule = _op_flops(node, in_avals, param_avals, out_avals)
        if node.op_name == "paged_attention" and len(in_avals) >= 5 \
                and len(in_avals[3].shape) == 2:
            # traffic = the page GATHER (K and V rows the table names),
            # not the whole physical pool the aval describes
            q, kp, pt = in_avals[0], in_avals[1], in_avals[3]
            page, hkv, d = (int(s) for s in kp.shape[-3:])
            S, P = (int(s) for s in pt.shape)
            item = np.dtype(kp.dtype).itemsize
            gather = 2 * S * P * page * hkv * d * item      # K + V
            in_bytes = (aval_bytes(q) + gather
                        + aval_bytes(pt) + aval_bytes(in_avals[4]))
        out.append(OpCost(i, node.op_name, rule,
                          flops if flops is not None else 0,
                          in_bytes, out_bytes, param_bytes,
                          modeled=flops is not None,
                          loc=graph.loc_of(i)))
    return out


# per-parameter-element FLOPs of the in-graph optimizer update
_OPT_FLOPS_PER_ELEM = {
    "SGD": 2, "Momentum": 4, "Adagrad": 8, "RMSProp": 10,
    "Adadelta": 10, "Adam": 18, "AdamW": 20, "Lamb": 24,
}


def _optimizer_flops(program: Program, trainable_bytes: int,
                     elem_size: int = 4) -> int:
    pack = program._optimizer
    if pack is None:
        return 0
    per = _OPT_FLOPS_PER_ELEM.get(type(pack[0]).__name__, 10)
    return per * (trainable_bytes // max(elem_size, 1))


# ---------------------------------------------------------------------------
# gradient-collective prediction (grad_comm wire bytes)
# ---------------------------------------------------------------------------

def _comm_block(program: Program, plan,
                graph: Optional[DefUseGraph] = None) -> Optional[dict]:
    """Predicted per-step gradient-communication cost of a training
    program under a sharding plan: per-collective wire bytes (quantized
    payload + scales), latency-vs-bandwidth classification, and the
    fp32 baseline.  With an active ``grad_comm`` spec the numbers come
    from the SAME ``plan_reduction`` the Executor compiles, so
    prediction and the runtime ``comm.wire_bytes`` stat agree exactly;
    without one, the block models GSPMD's default fp32 grad psum."""
    if program._optimizer is None or plan is None:
        return None
    from ...distributed import grad_comm as _gc
    from ...distributed.mesh import DP_AXIS
    from .liveness import _opt_unpack, param_array
    dp = dict(plan.mesh.shape).get(DP_AXIS, 1)
    # the SAME trainable filter the Executor differentiates with
    # (honors minimize's parameters=/no_grad_set) — the measured ==
    # predicted contract depends on the grad list matching exactly
    _opt, trainable = _opt_unpack(program)
    shapes = [tuple(param_array(p).shape) for p in trainable]
    grad_bytes = sum(4 * int(np.prod(s)) if s else 4 for s in shapes)
    ring = (2.0 * (dp - 1) / dp) if dp > 1 else 0.0
    fp32_wire = int(round(ring * grad_bytes))
    # the Executor's OWN activation predicate (shared, so measured and
    # predicted can never disagree about which path runs); a configured-
    # but-impossible spec is reported, not silently priced as fp32 —
    # the Executor will refuse to compile that program
    status, err = _gc.plan_status(plan)
    if status != "active":
        return {
            "enabled": False, "dp": dp, "dtype": "fp32",
            **({"error": err} if err else {}),
            # GSPMD's default grad psum sits after backward in the
            # schedule the compiler emits without a latency-hiding
            # scheduler — modeled as fully exposed (issue_frac 1)
            "overlap": "none", "overlap_path": "none",
            "wire_bytes_per_step": fp32_wire,
            "fp32_wire_bytes_per_step": fp32_wire,
            "gathers": [], "gather_wire_bytes_per_step": 0,
            "axis_wire_bytes": ({DP_AXIS: fp32_wire} if dp > 1
                                else {}),
            "collectives": ([] if dp <= 1 else [{
                "params": list(range(len(shapes))),
                "numel": grad_bytes // 4, "algorithm": "gspmd_psum",
                "wire_dtype": "fp32", "wire_bytes": fp32_wire,
                "collectives": 1, "classification": "bandwidth",
                "error_feedback": False, "issue_frac": 1.0}]),
        }
    cfg = plan.grad_comm
    # the SAME production order the Executor buckets with (backward
    # levels over the DefUseGraph) — bucket contents, and therefore
    # per-bucket wire bytes and issue points, cannot drift apart
    pack = program._optimizer
    order = _gc.production_order(program, trainable,
                                 pack[1] if pack is not None else None,
                                 graph=graph)
    # the SAME hybrid layout the Executor compiles (FSDP rscatter
    # buckets + forward gather schedule from the plan's own specs) —
    # per-axis prediction and the runtime comm.axis.<name>.wire_bytes
    # stats read one derivation
    named = [(p.name, s) for p, s in zip(trainable, shapes)]
    _kinds, fsdp, gathers = _gc.hybrid_layout(plan, named, order=order)
    gplan = _gc.plan_reduction(shapes, dp=dp, cfg=cfg, order=order,
                               fsdp=fsdp, gathers=gathers)
    return {
        "enabled": True, "dp": dp, "dtype": cfg.dtype,
        "block_size": cfg.block_size,
        "error_feedback": cfg.error_feedback,
        "overlap": cfg.overlap,
        "overlap_path": gplan.overlap_path,
        "wire_bytes_per_step": gplan.wire_bytes_per_step,
        "fp32_wire_bytes_per_step": gplan.fp32_wire_bytes_per_step,
        "collectives_per_step": gplan.collectives_per_step,
        "collectives": [b.to_dict() for b in gplan.buckets],
        "gathers": list(gplan.gathers),
        "gather_wire_bytes_per_step": gplan.gather_wire_bytes_per_step,
        "axis_wire_bytes": dict(gplan.axis_wire_bytes),
    }


def _comm_seconds(comm: dict, backward_s: float, ici_bw: float
                  ) -> Tuple[float, float]:
    """(total comm seconds, predicted EXPOSED comm seconds) of one
    comm block on a chip with ``ici_bw`` interconnect bandwidth.

    The exposed share follows the bucket schedule: bucket i's grads
    are complete at ``backward_s * issue_frac_i``, its collective then
    occupies the link after any earlier bucket's finishes, and
    whatever the link is still moving when backward ends is exposed —
    ``max(0, link_end - backward_s)``.  For a single bucket this is
    exactly ``max(0, comm_s - overlappable_backward_s)``.  With
    ``overlap_path == 'none'`` (or no overlap info) the whole stage is
    serialized after backward: exposed == total.

    Hybrid meshes add the forward param gathers
    (``gather_wire_bytes_per_step``): they always count toward the
    total; on an overlapping path they are issued ahead of each
    layer's forward in production order and hide behind forward
    compute, on the barriered path they serialize like everything
    else."""
    if ici_bw <= 0:
        return 0.0, 0.0
    gather_s = comm.get("gather_wire_bytes_per_step", 0) / ici_bw
    total = comm["wire_bytes_per_step"] / ici_bw + gather_s
    if not comm.get("enabled") or comm.get("overlap_path") == "none":
        return total, total
    link_end = 0.0
    for b in comm.get("collectives", ()):
        ready = backward_s * float(b.get("issue_frac", 1.0))
        link_end = max(link_end, ready) + b["wire_bytes"] / ici_bw
    return total, max(0.0, link_end - backward_s)


# ---------------------------------------------------------------------------
# shape re-derivation (concrete batch size)
# ---------------------------------------------------------------------------

def _propagate_avals(graph: DefUseGraph,
                     feed_shapes: Dict[str, Sequence[int]]
                     ) -> Dict[int, object]:
    """Re-derive every aval with concrete feed shapes by replaying each
    op through ``jax.eval_shape`` in topological order (the recorded
    placeholder for a dynamic dim is 1; costs scale with the real batch
    only when re-derived).  Falls back to the recorded aval for any op
    that fails to re-trace — the verifier owns reporting that."""
    import jax
    import jax.numpy as jnp

    from ...core.tensor import Parameter
    from ..program import replay_scope
    from .liveness import param_array

    avals: Dict[int, object] = {}
    for name, v in graph.feeds.items():
        shape = feed_shapes.get(name)
        if shape is None:
            avals[id(v)] = v.data
        else:
            avals[id(v)] = jax.ShapeDtypeStruct(
                tuple(int(s) for s in shape), np.dtype(v.data.dtype))

    def lookup(x):
        if isinstance(x, Parameter):
            arr = param_array(x)
            return jnp.zeros(arr.shape, arr.dtype)
        a = avals.get(id(x), x.data)
        return jnp.zeros(a.shape, a.dtype)

    for node in graph.nodes:
        args = []
        for tag, x in node.in_specs:
            if tag == "v":
                args.append(avals.get(id(x), x.data))
            elif tag == "p":
                arr = param_array(x)
                args.append(jax.ShapeDtypeStruct(tuple(arr.shape),
                                                 np.dtype(arr.dtype)))
            elif tag == "c":
                args.append(jax.ShapeDtypeStruct(tuple(x.shape),
                                                 np.dtype(x.dtype)))
            else:
                args.append(x)
        try:
            with replay_scope(lookup):
                derived = jax.eval_shape(
                    lambda *a, _n=node: _n.fn(*a, **_n.kw), *args)
        except Exception:  # noqa: BLE001 - verifier reports this class
            continue
        derived = list(derived) if node.multi else [derived]
        for v, a in zip(node.out_vars, derived):
            avals[id(v)] = a
    return avals


def _shapes_from_batch(graph: DefUseGraph, batch_size: int
                       ) -> Dict[str, Sequence[int]]:
    out = {}
    for name, v in graph.feeds.items():
        desc = v.desc_shape
        if desc and any(s == -1 for s in desc):
            out[name] = tuple(int(batch_size) if s == -1 else int(s)
                              for s in desc)
    return out


# ---------------------------------------------------------------------------
# fusion candidates
# ---------------------------------------------------------------------------

# an op that can ride a fused kernel's epilogue/prologue
_FUSABLE = (set(_ELEMENTWISE) | set(_REDUCE) | set(_NORMALIZE)
            | set(_LOSS) | _MOVEMENT | {"pool"})


def _fusion_candidates(graph: DefUseGraph, costs: List[OpCost],
                       avals: Dict[int, object], fetched: set,
                       top_k: int) -> List[dict]:
    """Maximal single-consumer chains, ranked by the HBM traffic a
    fused kernel saves: every intermediate that today is written by one
    op and read back by the next (2x its bytes) stays in registers/VMEM
    when the chain compiles as one kernel (the MPK selection rule)."""
    nodes = graph.nodes

    def bytes_of(v):
        return aval_bytes(avals.get(id(v), v.data))

    in_chain: set = set()
    cands: List[dict] = []
    for i in range(len(nodes)):
        if i in in_chain:
            continue
        chain = [i]
        j = i
        while True:
            outs = nodes[j].out_vars
            if len(outs) != 1:
                break
            v = outs[0]
            if id(v) in fetched:
                break
            cons = graph.consumers_of.get(id(v), [])
            if len(cons) != 1:
                break
            k = cons[0]
            if k <= j or k in in_chain or nodes[k].op_name not in _FUSABLE:
                break
            chain.append(k)
            j = k
        if len(chain) < 2:
            continue
        in_chain.update(chain)
        saved = sum(2 * bytes_of(nodes[j].out_vars[0])
                    for j in chain[:-1])
        unfused = sum(costs[j].total_bytes for j in chain)
        cands.append({
            "ops": chain,
            "op_names": [nodes[j].op_name for j in chain],
            "flops": sum(costs[j].flops for j in chain),
            "unfused_traffic_bytes": unfused,
            "fused_traffic_bytes": unfused - saved,
            "saved_bytes": saved,
            "loc": graph.loc_of(chain[0]),
        })
    cands.sort(key=lambda c: -c["saved_bytes"])
    return cands if top_k is None else cands[:top_k]


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"


def _fmt_flops(n: float) -> str:
    for unit in ("", "K", "M", "G", "T"):
        if abs(n) < 1000 or unit == "T":
            return f"{n:.2f}{unit}F" if unit else f"{int(n)}F"
        n /= 1000.0
    return f"{n:.2f}TF"


class ProgramReport:
    """Everything :func:`analyze` learned about one Program."""

    __slots__ = ("program_serial", "n_ops", "fetch_names", "per_op",
                 "totals", "memory", "memory_per_shard", "roofline",
                 "fusion_candidates", "hazards", "batch_hint")

    def to_dict(self) -> dict:
        return {
            "program": self.program_serial,
            "ops": self.n_ops,
            "fetch": list(self.fetch_names),
            "batch_hint": self.batch_hint,
            "per_op": [c.to_dict() for c in self.per_op],
            "totals": self.totals,
            "memory": self.memory.to_dict(),
            "memory_per_shard": (None if self.memory_per_shard is None
                                 else self.memory_per_shard.to_dict()),
            "roofline": self.roofline,
            "fusion_candidates": self.fusion_candidates,
            "hazards": [d.to_dict() for d in self.hazards],
        }

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    # -- text rendering ----------------------------------------------------
    def render(self, max_rows: Optional[int] = 40) -> str:
        t, m = self.totals, self.memory
        lines = [f"Program #{self.program_serial}: {self.n_ops} ops, "
                 f"fetch={list(self.fetch_names)}"]
        lines.append(
            f"  flops: fwd {_fmt_flops(t['flops_fwd'])}"
            + (f", train {_fmt_flops(t['flops_train'])}"
               if t["flops_train"] is not None else "")
            + f" | min HBM traffic {_fmt_bytes(t['min_traffic_bytes'])}"
            f" | arithmetic intensity {t['arithmetic_intensity']:.1f}")
        un = t["unmodeled"]
        lines.append(
            f"  unmodeled: {un['count']} op(s), {_fmt_bytes(un['bytes'])}"
            + (f" ({', '.join(sorted(set(un['ops'])))})" if un["ops"]
               else ""))
        lines.append(
            f"  memory: peak {_fmt_bytes(m.peak_bytes_donated)} donated / "
            f"{_fmt_bytes(m.peak_bytes_no_donation)} no-donation "
            f"(params {_fmt_bytes(m.param_bytes)}, slots "
            f"{_fmt_bytes(m.slot_bytes)}, grads {_fmt_bytes(m.grad_bytes)}, "
            f"activations {_fmt_bytes(m.retained_activation_bytes if m.training else m.activation_peak_bytes)})")
        ms = self.memory_per_shard
        if ms is not None:
            lines.append(
                f"  per-shard ({self.totals.get('mesh_devices', '?')} "
                f"devices): peak {_fmt_bytes(ms.peak_bytes_donated)} "
                f"donated / {_fmt_bytes(ms.peak_bytes_no_donation)} "
                f"no-donation (params {_fmt_bytes(ms.param_bytes)}, "
                f"slots {_fmt_bytes(ms.slot_bytes)}, grads "
                f"{_fmt_bytes(ms.grad_bytes)})")
        comm = self.totals.get("comm")
        if comm is not None:
            ratio = (comm["wire_bytes_per_step"]
                     / max(comm["fp32_wire_bytes_per_step"], 1))
            lines.append(
                f"  comm (dp={comm['dp']}, "
                f"{'grad_comm ' + str(comm['dtype']) if comm['enabled'] else 'gspmd fp32'}): "
                f"{_fmt_bytes(comm['wire_bytes_per_step'])}/step wire "
                f"({ratio:.2f}x fp32), "
                f"{len(comm['collectives'])} collective group(s), "
                f"overlap {comm.get('overlap', 'none')}"
                f"->{comm.get('overlap_path', 'none')}")
        if self.roofline:
            lines.append("  roofline (predicted):")
            for name, r in self.roofline.items():
                split = ""
                if r.get("predicted_comm_s") is not None:
                    split = (
                        f", comm {r['predicted_comm_s'] * 1e3:.3f} ms "
                        f"(exposed "
                        f"{r['predicted_exposed_comm_s'] * 1e3:.3f} / "
                        f"hidden "
                        f"{r['predicted_hidden_comm_s'] * 1e3:.3f})")
                lines.append(
                    f"    {name:>4}: step {r['predicted_step_s'] * 1e3:.3f} ms, "
                    f"MFU {r['predicted_mfu']:.3f}, {r['bound']}-bound"
                    + split)
        if self.fusion_candidates:
            n_real = sum(1 for c in self.fusion_candidates
                         if c.get("realized"))
            lines.append(
                f"  fusion candidates (by HBM traffic saved; "
                f"{n_real}/{len(self.fusion_candidates)} realized by "
                f"the Pallas tier):")
            for c in self.fusion_candidates:
                loc = f" @ {c['loc']}" if c.get("loc") else ""
                real = (f" [realized: {c['realized']}]"
                        if c.get("realized") else "")
                lines.append(
                    f"    {'+'.join(c['op_names'])} (ops {c['ops']}): "
                    f"saves {_fmt_bytes(c['saved_bytes'])}{loc}{real}")
        if self.hazards:
            lines.append("  hazards:")
            for d in self.hazards:
                lines.append(f"    {d}")
        rows = self.per_op if max_rows is None \
            else self.per_op[:max_rows]
        lines.append("  per-op:")
        lines.append("    idx  op                    flops        bytes"
                     "      rule")
        for c in rows:
            star = " " if c.modeled else "*"
            lines.append(
                f"    {c.op_index:>3}{star} {c.op_name:<20} "
                f"{_fmt_flops(c.flops):>10} {_fmt_bytes(c.total_bytes):>10}"
                f"  {c.rule}" + (f"  @ {c.loc}" if c.loc else ""))
        if max_rows is not None and len(self.per_op) > max_rows:
            lines.append(f"    ... {len(self.per_op) - max_rows} more "
                         f"(render(max_rows=None))")
        return "\n".join(lines)

    def __str__(self):
        return self.render()

    def __repr__(self):
        t = self.totals
        return (f"ProgramReport(#{self.program_serial}, {self.n_ops} ops, "
                f"fwd={_fmt_flops(t['flops_fwd'])}, "
                f"peak={_fmt_bytes(self.memory.peak_bytes_donated)})")


def analyze(program: Program, fetch_list: Optional[Sequence] = None,
            feed_shapes: Optional[Dict[str, Sequence[int]]] = None,
            batch_size: Optional[int] = None,
            chip: Optional[str] = None, top_k: Optional[int] = 5,
            include_hazards: bool = True,
            sharding=None) -> ProgramReport:
    """Quantitative analysis of one recorded Program.

    ``fetch_list`` (Variables or names) roots the liveness analysis;
    with an attached optimizer the loss is an implicit root.
    ``batch_size`` substitutes every dynamic feed dim (declared None/-1)
    and re-derives all avals; ``feed_shapes`` overrides specific feeds
    exactly.  ``chip`` selects one roofline spec from
    :data:`CHIP_SPECS` (default: the whole table).  ``top_k`` bounds
    the ranked fusion candidates (0 = none, None = all).  ``sharding``
    (a :class:`~paddle_tpu.distributed.sharding.ShardingPlan`) adds
    ``memory_per_shard``: each tensor's bytes divided by the mesh-axis
    sizes its PartitionSpec shards over — the report then prices the
    program per-chip, not per-fleet."""
    graph = DefUseGraph(program)

    shapes = dict(feed_shapes or {})
    if batch_size is not None:
        derived = _shapes_from_batch(graph, batch_size)
        derived.update(shapes)
        shapes = derived
    avals = _propagate_avals(graph, shapes) if shapes else {}

    fetch_vars: List[Variable] = []
    fetch_names: List[str] = []
    for f in (fetch_list or []):
        v = graph.resolve_fetch(f)
        if v is not None:
            fetch_vars.append(v)
            fetch_names.append(v.name)
    opt_pack = program._optimizer
    if opt_pack is not None and isinstance(opt_pack[1], Variable) \
            and not any(v is opt_pack[1] for v in fetch_vars):
        fetch_vars.append(opt_pack[1])

    costs = _node_costs(graph, avals)
    memory = estimate_memory(graph, fetch_vars, avals)
    memory_per_shard = None
    if sharding is not None:
        # per-shard accounting: params (and their grads + slots) divide
        # by their spec's axis-size product, activations/feeds by the
        # batch-axis product — but only when the plan actually shards
        # every feed (a non-divisible feed replicates: each chip holds
        # the FULL array, so dividing would underreport per-chip peak)
        seen_p: Dict[int, int] = {}
        all_params = graph.program.parameters()
        spec_of = dict(zip(sharding.param_names, sharding.param_specs))
        for pos, p in enumerate(all_params):
            spec = spec_of.get(p.name)
            if spec is None and pos < len(sharding.param_specs):
                spec = sharding.param_specs[pos]
            seen_p[id(p)] = sharding.divisor(spec) if spec is not None \
                else 1

        from ...distributed.sharding import spec_axes

        def _feed_shape(v):
            a = avals.get(id(v), v.data)
            return tuple(a.shape)

        feeds_sharded = all(
            len(spec_axes(sharding.feed_spec(_feed_shape(v)))) > 0
            for v in graph.feeds.values()) if graph.feeds else True
        memory_per_shard = estimate_memory(
            graph, fetch_vars, avals, param_div=seen_p,
            act_div=sharding.batch_divisor() if feeds_sharded else 1)

    flops_fwd = sum(c.flops for c in costs)
    unmodeled = [c for c in costs if not c.modeled]
    training = opt_pack is not None
    opt_flops = _optimizer_flops(program, memory.trainable_param_bytes)
    flops_train = (3 * flops_fwd + opt_flops) if training else None

    def bytes_of(v):
        return aval_bytes(avals.get(id(v), v.data))

    feed_bytes = memory.feed_bytes
    fetch_bytes = sum(bytes_of(v) for v in fetch_vars)
    unfused_traffic = sum(c.total_bytes for c in costs)
    if training:
        # fwd reads params+feeds, bwd writes grads, update reads grads +
        # params + slots and writes params + slots; retained activations
        # (op outputs only — feeds ride feed_bytes once) are written
        # once and read back once by the backward.  The fetched loss is
        # both an op output and a fetch: epsilon double-count for the
        # scalar losses this models.
        min_traffic = (feed_bytes + fetch_bytes
                       + 3 * memory.trainable_param_bytes
                       + (memory.param_bytes
                          - memory.trainable_param_bytes)
                       + 2 * memory.slot_bytes
                       + 2 * memory.retained_activation_bytes)
        roof_flops = flops_train
    else:
        min_traffic = feed_bytes + fetch_bytes + memory.param_bytes
        roof_flops = flops_fwd
    intensity = roof_flops / max(min_traffic, 1)

    comm = _comm_block(program, sharding, graph=graph) \
        if sharding is not None else None

    if chip is not None:
        if chip not in CHIP_SPECS:
            raise KeyError(
                f"unknown chip {chip!r}; known: {sorted(CHIP_SPECS)}")
        specs = {chip: CHIP_SPECS[chip]}
    else:
        specs = CHIP_SPECS
    roofline = {}
    for name, spec in specs.items():
        t_comp = roof_flops / spec.peak_flops
        t_mem = min_traffic / spec.hbm_bw
        step = max(t_comp, t_mem)
        entry = {
            "peak_flops": spec.peak_flops,
            "hbm_bw": spec.hbm_bw,
            "predicted_step_s": step,
            "predicted_mfu": (t_comp / step) if step > 0 else 0.0,
            "bound": "compute" if t_comp >= t_mem else "memory",
            "fits_hbm": memory.peak_bytes_donated <= spec.hbm_bytes,
        }
        if comm is not None and training and comm.get("dp", 1) > 1:
            # overlap-aware step time: only the EXPOSED share of the
            # gradient collectives adds to the step — comm that hides
            # behind backward costs nothing.  Backward's window is its
            # FLOP share of the compute-only step (2x the forward of
            # the 3x-fwd training total).
            backward_s = step * (2.0 * flops_fwd / max(roof_flops, 1))
            comm_s, exposed_s = _comm_seconds(comm, backward_s,
                                              spec.ici_bw)
            entry["predicted_comm_s"] = comm_s
            entry["predicted_exposed_comm_s"] = exposed_s
            entry["predicted_hidden_comm_s"] = comm_s - exposed_s
            entry["predicted_step_s"] = step + exposed_s
            if entry["predicted_step_s"] > 0:
                entry["predicted_mfu"] = (
                    t_comp / entry["predicted_step_s"])
        roofline[name] = entry

    fetched_ids = {id(v) for v in fetch_vars}
    cands = _fusion_candidates(graph, costs, avals, fetched_ids, top_k)
    if cands:
        # mark what the executor's epilogue-fusion pass realizes for
        # each candidate under the current flags (same matcher, same
        # gates — prediction and execution cannot disagree); the
        # report then separates realized from still-unrealized savings.
        # Under a sharding plan the executor skips the pass entirely
        # (pallas_call below an explicit GSPMD lowering is unsupported)
        # — the report must say so too, hence plan_active.
        from .fusion import annotate_candidates
        annotate_candidates(program, cands, graph, avals, fetched_ids,
                            plan_active=sharding is not None)

    hazards: List[Diagnostic] = []
    if include_hazards:
        from .hazards import hazard_passes
        for p in hazard_passes():
            hazards.extend(p.run(graph, fetch_list))

    rep = ProgramReport()
    rep.program_serial = program._serial
    rep.n_ops = len(graph.nodes)
    rep.fetch_names = fetch_names
    rep.batch_hint = batch_size
    rep.per_op = costs
    rep.memory_per_shard = memory_per_shard
    rep.totals = {
        **({"mesh_devices": sharding.n_devices}
           if sharding is not None else {}),
        **({"comm": comm} if comm is not None else {}),
        "flops_fwd": flops_fwd,
        "flops_train": flops_train,
        "optimizer_flops": opt_flops if training else 0,
        "feed_bytes": feed_bytes,
        "fetch_bytes": fetch_bytes,
        "param_bytes": memory.param_bytes,
        "unfused_traffic_bytes": unfused_traffic,
        "min_traffic_bytes": min_traffic,
        "arithmetic_intensity": intensity,
        "unmodeled": {
            "count": len(unmodeled),
            "ops": [c.op_name for c in unmodeled],
            "bytes": sum(c.total_bytes for c in unmodeled),
            "flops_unknown": bool(unmodeled),
        },
    }
    rep.memory = memory
    rep.roofline = roofline
    rep.fusion_candidates = cands
    rep.hazards = hazards
    return rep


# jax ``device_kind`` -> CHIP_SPECS key
_KIND_TO_CHIP = {"TPU v4": "v4", "TPU v5 lite": "v5e", "TPU v5e": "v5e",
                 "TPU v5p": "v5p", "TPU v5": "v5p"}


def resolve_perf_chip() -> str:
    """The ``CHIP_SPECS`` key runtime predictions are priced against:
    ``FLAGS_perf_chip`` when set, else the spec of the device jax
    reports (``cpu`` on the CPU backend, by ``device_kind`` on a TPU).
    A flag value or a device kind with no spec raises — a prediction
    priced against some other chip's roofline is worse than none.  The
    single policy both ``compile_summary`` and the perf observatory's
    drift fallback use — one place to extend when a chip is added."""
    from ...core.flags import get_flag
    chip = get_flag("perf_chip")
    if chip:
        if chip not in CHIP_SPECS:
            raise ValueError(
                f"FLAGS_perf_chip={chip!r} is not a known chip spec "
                f"(choose from {sorted(CHIP_SPECS)})")
        return chip
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return "cpu"
    key = _KIND_TO_CHIP.get(dev.device_kind)
    if key is None:
        raise ValueError(
            f"no roofline chip spec for device kind {dev.device_kind!r} "
            f"(platform {dev.platform!r}); add one to CHIP_SPECS or set "
            f"FLAGS_perf_chip to one of {sorted(CHIP_SPECS)}")
    return key


def compile_summary(program: Program, donate: bool = True,
                    sharding=None) -> Optional[dict]:
    """The light, always-on slice the Executor records per compile:
    predicted FLOPs per step + peak bytes from the recorded avals (no
    re-derivation, no hazard passes), plus the roofline's predicted
    step time for the chip this process is actually running on
    (``FLAGS_perf_chip``, auto-detected backend by default) — the
    number the perf observatory's drift tracker compares measured
    steps against.  With a ``sharding`` plan the summary also carries
    ``peak_bytes_per_shard`` — what one chip actually holds.  A chip
    with no spec raises (see :func:`resolve_perf_chip`); a gap in the
    cost model itself returns None and counts
    ``predicted.executor.errors`` — it must never break a compile, but
    an unpriced compile must not pass unnoticed either."""
    chip = resolve_perf_chip()
    try:
        rep = analyze(program, include_hazards=False, chip=chip,
                      top_k=0, sharding=sharding)
    except Exception:  # noqa: BLE001 - prediction is best-effort
        from ...utils import monitor
        monitor.stat_add("predicted.executor.errors")
        return None
    t = rep.totals
    peak = (rep.memory.peak_bytes_donated if donate
            else rep.memory.peak_bytes_no_donation)
    out = {
        "flops": (t["flops_train"] if t["flops_train"] is not None
                  else t["flops_fwd"]),
        "flops_fwd": t["flops_fwd"],
        "peak_bytes": peak,
        "min_traffic_bytes": t["min_traffic_bytes"],
        "chip": chip,
        "predicted_step_s": rep.roofline[chip]["predicted_step_s"],
        "unmodeled_ops": t["unmodeled"]["count"],
        "unmodeled_bytes": t["unmodeled"]["bytes"],
    }
    if rep.memory_per_shard is not None:
        ms = rep.memory_per_shard
        out["peak_bytes_per_shard"] = (
            ms.peak_bytes_donated if donate
            else ms.peak_bytes_no_donation)
        out["mesh_devices"] = t.get("mesh_devices")
    comm = t.get("comm")
    if comm is not None:
        # predicted gradient wire bytes per step ride the compile
        # record next to predicted_step_s — the number the runtime's
        # comm.wire_bytes stat is compared against
        out["predicted_wire_bytes"] = comm["wire_bytes_per_step"]
        out["comm_enabled"] = comm["enabled"]
        # per-mesh-axis prediction (hybrid meshes): what the runtime's
        # comm.axis.<name>.wire_bytes stats must measure, axis by axis
        if comm.get("axis_wire_bytes"):
            out["predicted_axis_wire_bytes"] = dict(
                comm["axis_wire_bytes"])
        if comm.get("gather_wire_bytes_per_step"):
            out["predicted_gather_wire_bytes"] = \
                comm["gather_wire_bytes_per_step"]
        # the overlap prediction (total/exposed/hidden comm seconds on
        # the running chip + the resolved path) — what the perf
        # observatory's exposed-vs-hidden split reads per step
        out["comm_overlap"] = comm.get("overlap_path", "none")
        r = rep.roofline[chip]
        for k in ("predicted_comm_s", "predicted_exposed_comm_s",
                  "predicted_hidden_comm_s"):
            if k in r:
                out[k] = r[k]
    return out
