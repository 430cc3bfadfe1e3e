"""A routed mixture of experts, told which experts it holds.

The router spans ALL experts of the layer (``router_w`` [H, E]): logits,
scores and top-k in float32, because a routing decision is discrete.  The
scores are a softmax over the experts or a sigmoid of each logit
(``scoring``); a per-expert bias may be added for the selection alone
(the gates stay the chosen scores': DeepSeek-V3's bias-corrected
selection), and the gates may carry a scaling factor.
The expert weights are the slice this device holds, stacked
``[held, ...]``: experts ``first .. first + held - 1`` of the E.  A
token's gates are renormalised over its ``top_k`` experts whatever is
held (``norm_topk_prob``); the layer returns the part of the result that
the held experts give, and what the absent ones would add is left out
(the caller of an expert-parallel group sums the parts; on one chip
nothing stands in for the absent chips).  A shared expert, where the
layer has one, is one expert of the layer's form over every token added
to that part: every member of a group computes it alike.

An expert has one of two forms, told by its weights: with a gate it is a
SwiGLU, ``(silu(x Wg) * (x Wu)) Wd``; without one (``w_gate`` None) it is
``relu(x Wu)^2 Wd`` (Nemotron-H's ``relu2``), which runs two products
where the SwiGLU runs three and has no gate leaf at all.

A member that runs WITHOUT its group can hold its routers still
(``train_router=False``: the gates are constants of the backward pass).
The gates' gradient says which of a token's chosen experts to prefer,
and needs every chosen expert's <dL/dy, expert(x)>.  One member has its
own experts' terms; the absent experts' read as zero, so every step
says "prefer the held ones", and Adam turns that into a held load that
grows several-fold within tens of steps (PERF.md section 6, PR 32).
What a member could learn alone is the preference among held experts
that one token chose together.

No capacity, no dropped token.  The (token, slot) assignments are sorted
by held expert, the absent ones last; the tokens of the held ones are
gathered into one [rows, H] buffer, the expert's products run as
grouped matmuls over it (``jax.lax.ragged_dot``: on a TPU XLA lowers it
to a Mosaic kernel that walks only the tiles the group sizes cover, so
its cost follows the load, not the buffer), and the result goes back by
a gate-weighted sum over a token's slots.  The buffer has to take every
assignment of every token (imbalance may send them all here), so the
tokens are walked in chunks of one sequence of the batch; a chunk's
buffer is a little over twice what an even router would send here where
its assignments fit that, and ``tokens * top_k`` rows where they do not
(``_chunk``: a ``lax.cond`` on the load, in the forward and in the
backward pass alike).

What each pass of a chunk costs by (n tokens, K = ``top_k``, a buffer of
R rows of width H).  From tokens to rows, R gathered rows each: the
tokens of the rows (``_dispatch``, run again in the backward pass) and
the result's gradient of the rows, which ``dy`` and the gates' gradient
are made from in one pass (``_combine_bwd``: one float32 number a row;
only numbers go back to [n, K]).  From rows to tokens, the two sums over
a token's slots (``_token_sums``: the gate-weighted one of the result,
the plain one that is the transpose of ``_dispatch``; autodiff would emit
a scatter-add of rows, which a TPU runs a row at a time): with a small
buffer on a TPU the rows are put in token order, one more gather of R
rows, and summed by a kernel that streams them once
(ops/pallas/moe_combine.py), so **no pass of such a chunk reads or
writes n * K rows of H**.  XLA's form of the sums gathers a row for
every slot, held or not, and multiplies the absent ones by zero: n * K
rows of which at most R are ever used.  It stays where the kernel does
not (off a TPU, and the tests' reference) and for the full buffer, where
R = n * K and the gather by ``pos`` IS the rows in token order (the
kernel would be work on top of it, and a second [n * K, H] array).
Numbers cost as rows do, about 7 ns each gathered on a v5e, so the
kernel's form moves as few as it can: a row's gate rides the sort as a
third operand, and again the sort into token order; ``pos`` (a scatter
of n * K numbers) is not made; a row's ``dc`` is scattered to its own
place (PERF.md section 6, PR 46, has every pass's time).

Inside a step that collects device counters (``jit.TrainStep``) a call
reports its load: ``moe.expert_load``, ``moe.chunk_assignments``,
``moe.full_buffer_chunks`` (``_count_load``; observability/scopes.py),
from the group sizes the products are given.  Elsewhere nothing is
counted and the program is what it was.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..observability import device_counters, scopes
from ..utils import monitor
from .pallas.moe_combine import (moe_combine, moe_combine_supported,
                                 padded_rows)
from .pallas.support import choose_kernel

__all__ = ["moe_route", "moe_experts", "moe_forward"]


@jax.named_scope(scopes.MOE_ROUTER)
def moe_route(x32, router_w, top_k, norm_topk_prob=True, scoring="softmax",
              bias=None, scaling=1.0, gate_epsilon=None):
    """x32 [N, H] float32 -> (gates [N, top_k] float32, expert ids
    [N, top_k] int32 over all E).  The matmul at full float32 precision:
    a TPU's default for float32 operands is one bfloat16 pass.
    ``scoring``: "softmax" over the experts or "sigmoid" of each logit.
    ``bias`` [E] joins the scores for the selection only (no gradient
    reaches it); the gates are the chosen experts' scores, renormalised
    under ``norm_topk_prob`` and multiplied by ``scaling``.  The
    normalisation divides by the chosen scores' sum plus ``gate_epsilon``,
    a family's own constant (LFM2's public code adds 1e-6); None: nothing
    for a softmax, DeepSeek-V3's floor of 1e-20 for sigmoids.  Equal
    selection values: the lower expert index."""
    logits = jax.lax.dot_general(
        x32.astype(jnp.float32), router_w.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring {scoring!r}: 'softmax' or 'sigmoid'")
    if bias is None:
        gates, ids = jax.lax.top_k(scores, top_k)
    else:
        _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(scores, ids, -1)
    if norm_topk_prob:
        total = jnp.sum(gates, -1, keepdims=True)
        if gate_epsilon is not None:
            total = total + gate_epsilon
        elif scoring == "sigmoid":
            total = total + 1e-20   # DeepSeek-V3's floor: eight may vanish
        gates = gates / total
    if scaling != 1.0:
        gates = gates * scaling
    return gates, ids.astype(jnp.int32)


def _hidden(gate, up):
    """An expert's hidden state in float32 from its first products:
    ``silu(gate) * up``, or ``relu(up)^2`` where it has no gate."""
    up = up.astype(jnp.float32)
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(gate.astype(jnp.float32)) * up


@jax.named_scope(scopes.MOE_SHARED)
def _shared_expert(x, w_gate, w_up, w_down):
    """x [N, H] in the weights' type -> one expert over every token,
    float32: a SwiGLU, or ``relu(x Wu)^2 Wd`` where ``w_gate`` is None."""
    def dot(a, w):
        return jnp.dot(a, w, preferred_element_type=jnp.float32)

    a = _hidden(None if w_gate is None else dot(x, w_gate),
                dot(x, w_up)).astype(x.dtype)
    return dot(a, w_down)


# -- between tokens and rows: two gathers, two sums ---------------------------

class _Route(NamedTuple):
    """Where a chunk's assignments lie in its buffer of R rows."""
    at: jax.Array       # [R]: the assignment (token * K + slot) in row r
    gate: jax.Array     # [R] float32: its gate, 0 where its expert is not
    #                     held; a constant (``_combine`` makes the gradient)
    live: jax.Array     # [n, K]: whether the slot's expert is held
    pos: jax.Array | None    # [n, K]: the row of assignment (token, slot),
    #                          where the sums run in XLA's form
    order: tuple | None      # ``_token_order``'s, where through the kernel


def _token_order(at, gate, held_rows, P):
    """The rows of a buffer in token order, for the kernel's form of
    ``_token_sums``.  ``at`` and ``gate`` [R] as in ``_Route``, the first
    ``held_rows`` rows holding something.  An assignment's index IS
    token-major, so sorting the rows by it puts them in token order, the
    rows that hold nothing last, in whole tiles.  -> (the assignment in
    each place [R'], P where none; its row [R']; its gate [R'])."""
    R = at.shape[0]
    pad = (0, padded_rows(R) - R)
    r = jnp.arange(R + pad[1], dtype=jnp.int32)
    at, by_tok, gate = jax.lax.sort(
        (jnp.where(r < held_rows, jnp.pad(at, pad), P), r,
         jnp.pad(gate, pad)), num_keys=1)
    return at, jnp.minimum(by_tok, R - 1), gate


def _token_sums(rows, c, route, dtype):
    """rows [R, H] -> [n, H] of ``dtype``: each token's held slots' rows,
    times the gates ``c`` [n, K] float32 where they are given, summed in
    float32.  XLA's form gathers a row for every slot, n * K of them; the
    kernel's walks the R rows in token order (ops/pallas/moe_combine.py)."""
    if route.order is None:
        picked = rows[route.pos]
        picked = (jnp.where(route.live[..., None], picked, 0) if c is None
                  else c[..., None] * picked.astype(jnp.float32))
        return jnp.sum(picked, 1, dtype=jnp.float32).astype(dtype)
    at, by_tok, gate = route.order
    n, K = route.live.shape
    # a place that holds nothing belongs to token n: the kernel skips it
    return moe_combine(rows[by_tok], at // K, None if c is None else gate,
                       n, dtype)


def _tokens_of(route):
    """[R]: the token whose slot row r holds."""
    return route.at // route.live.shape[1]


@jax.custom_vjp
def _dispatch(x, route):
    """x [n, H] -> rows [R, H]: row r is the token of sorted assignment
    r."""
    return x[_tokens_of(route)]


def _dispatch_fwd(x, route):
    return _dispatch(x, route), route


def _dispatch_bwd(route, d_rows):
    return _token_sums(d_rows, None, route, d_rows.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, c, route):
    """out[t] = sum_k c[t, k] * y[pos[t, k]]; ``c`` [n, K] float32 is the
    gate, zero where the slot's expert is not held."""
    return _token_sums(y, c, route, jnp.float32)


def _combine_fwd(y, c, route):
    return _combine(y, c, route), (y, c, route)


def _combine_bwd(res, d_out):
    y, c, route = res
    d_tok = d_out[_tokens_of(route)]                      # [R, H] f32
    dy = (route.gate[:, None] * d_tok).astype(y.dtype)
    # the gate's gradient, through the held slots only
    if route.order is None:
        dc = jnp.sum(d_out[:, None, :]
                     * y[route.pos].astype(jnp.float32), -1)
    else:
        # one number a row, made in the pass that makes ``dy``, and only
        # numbers go back to [n, K], each to its own place.  The barrier
        # holds the two together: nothing else reads ``d_tok`` [R, H]
        # float32, and left to itself XLA puts the sum off to the branch's
        # end and keeps ``d_tok`` and ``y`` until then (the Keye step
        # compiled 0.39 GB larger so)
        dy, dc = jax.lax.optimization_barrier(
            (dy, jnp.sum(d_tok * y.astype(jnp.float32), -1)))
        dc = jnp.zeros(c.size, jnp.float32).at[route.at].set(
            dc, unique_indices=True).reshape(c.shape)
    return dy, jnp.where(c != 0, dc, 0.0), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _grouped(rows, w, sizes):
    """rows [P, K] @ w [held, K, N] by groups of ``sizes`` rows.  The
    precision is explicit, as for the Pallas tier's own matmuls
    (support.dot): XLA's TPU kernel for this takes bfloat16 operands in
    one pass only, whatever ``jax_default_matmul_precision`` says."""
    prec = (jax.lax.Precision.DEFAULT if rows.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)
    return jax.lax.ragged_dot(rows, w, sizes, precision=prec,
                              preferred_element_type=rows.dtype)


def moe_experts(x, gates, local, w_gate, w_up, w_down, rows=None):
    """One chunk.  x [n, H]; gates [n, K] float32; ``local`` [n, K]: the
    slot's expert as an index into the held stack, ``held`` (one past
    the last) where it is not held; ``w_gate`` None: experts without a
    gate (the header's second form).  ``rows``: the size of the experts'
    buffer, which must take every held assignment of the chunk (n * K,
    the default, always does).  -> [n, H] float32."""
    return _experts(x, gates, local, w_gate, w_up, w_down, rows)[0]


def _experts(x, gates, local, w_gate, w_up, w_down, rows):
    """``moe_experts`` with the groups' sizes, which the grouped products
    need anyway: -> ([n, H] float32, [held] int32, the assignments each
    held expert got)."""
    n, K = local.shape
    held = w_up.shape[0]
    P = n * K
    R = P if rows is None else rows
    # the full buffer keeps XLA's form: with every row in it a token's
    # rows ARE the gather by ``pos``, and the kernel would be work on top
    in_token_order = R < P and choose_kernel(
        "moe_combine", moe_combine_supported(n, x.shape[1], x.dtype))
    if R < P:
        monitor.stat_set("moe.token_major_rows", R if in_token_order else P)
    with jax.named_scope(scopes.MOE_DISPATCH):
        flat = local.reshape(P)
        live = local < held
        c = jnp.where(live, gates, 0.0)
        # held ones first, a row's gate beside it
        _, at, gate = jax.lax.sort(
            (flat, jnp.arange(P, dtype=jnp.int32),
             jax.lax.stop_gradient(c).reshape(P)),
            num_keys=1, is_stable=True)
        sizes = jnp.sum(flat[:, None] == jnp.arange(held)[None, :], 0,
                        dtype=jnp.int32)
        if in_token_order:
            pos = None
            order = _token_order(at[:R], gate[:R], jnp.sum(sizes), P)
        else:
            # the row of assignment (token, slot); the absent ones' rows
            # lie past the held ones' and are never read
            order = None
            pos = jnp.minimum(jnp.zeros(P, jnp.int32).at[at].set(
                jnp.arange(P, dtype=jnp.int32)), R - 1).reshape(n, K)
        route = _Route(at[:R], gate[:R], live, pos, order)
        x_rows = _dispatch(x, route)
    with jax.named_scope(scopes.MOE_EXPERTS):
        g = None if w_gate is None else _grouped(x_rows, w_gate, sizes)
        a = _hidden(g, _grouped(x_rows, w_up, sizes)).astype(x_rows.dtype)
        y = _grouped(a, w_down, sizes)
    with jax.named_scope(scopes.MOE_DISPATCH):
        # rows past the last held assignment belong to no group: whatever
        # the grouped product left there is not read (XLA's form multiplies
        # it by a gate of zero; the kernel's leaves such rows out itself)
        if not in_token_order:
            y = jnp.where((jnp.arange(R) < jnp.sum(sizes))[:, None], y, 0)
        return _combine(y, c, route), sizes


# A chunk's buffer for the load it has.  Every gather of rows costs by the
# buffer's rows, live or not (the header's list: with the kernel five
# gathers of R rows a chunk, forward and backward, and none of n * K; in
# XLA's form three of R and three of n * K, whatever R is.  One v5e, 8192
# tokens of 2048, PERF.md PR 30: XLA's gate-weighted gather back takes
# 1.07 ms from 16,384 rows and 2.82 ms from 65,536), so the common case
# should not pay for the worst: a small buffer of ``_ROOM`` times the rows
# a router that spreads its tokens evenly over all experts sends to the
# held ones, and every assignment of every token (always enough) where
# the chunk's load is larger.
_ROOM = 2.25


def _small_buffer(n, top_k, held, total):
    """Rows of the small buffer of a chunk of n tokens, None where it
    would not be smaller than the full one (most experts held)."""
    rows = int(_ROOM * n * top_k * held / total)
    return rows if rows < n * top_k else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 7))
def _chunk(small, x, gates, local, w_gate, w_up, w_down, counting=False):
    """``moe_experts`` with the buffer chosen by the chunk's load, and no
    residual but its arguments: the backward pass runs the forward again
    inside the branch it takes, so no [rows, H] buffer outlives a chunk
    and the branch not taken leaves nothing behind.  ``counting``: the
    groups' sizes [held] are a second result (for ``_count_load``)."""
    def fn(rows):
        out, sizes = _experts(x, gates, local, w_gate, w_up, w_down, rows)
        return (out, sizes) if counting else out

    return _by_load(small, local, w_up.shape[0], fn)


def _by_load(small, local, held, fn):
    """``fn(small)`` where the chunk's held assignments fit ``small``
    rows, ``fn(None)`` (the full buffer) where they do not."""
    if small is None:
        return fn(None)
    return jax.lax.cond(jnp.sum(local < held) <= small,
                        functools.partial(fn, small),
                        functools.partial(fn, None))


def _chunk_fwd(small, x, gates, local, w_gate, w_up, w_down, counting):
    return (_chunk(small, x, gates, local, w_gate, w_up, w_down, counting),
            (x, gates, local, w_gate, w_up, w_down))


def _chunk_bwd(small, counting, res, d_out):
    x, gates, local, w_gate, w_up, w_down = res
    if counting:
        d_out = d_out[0]          # the sizes are counts: no cotangent

    def grads(rows):
        _, pull = jax.vjp(
            lambda x, gates, wg, wu, wd: moe_experts(x, gates, local, wg, wu,
                                                     wd, rows),
            x, gates, w_gate, w_up, w_down)
        return pull(d_out)

    # XLA names the fusions inside a branch after the branch: without a
    # scope here the backward's gathers would read as plain ``moe`` time
    # (the grouped products are kernels with names of their own)
    with jax.named_scope(scopes.MOE_DISPATCH):
        dx, dgates, dwg, dwu, dwd = _by_load(small, local, w_up.shape[0],
                                             grads)
    return dx, dgates, None, dwg, dwu, dwd


_chunk.defvjp(_chunk_fwd, _chunk_bwd)


# XLA's grouped-matmul kernel is slow at an expert width that is no
# multiple of 256.  One v5e, 3,072 of a 6,912-row buffer live, 8 experts
# of [2688, F], bfloat16, ms for the up and the down product and for the
# gradients of each: F 1856 2.65 / 2.55 / 6.77 / 5.80, 1920 2.74 / 2.81 /
# 6.23 / 7.06, 2048 1.41 / 1.66 / 3.75 / 3.68; the pad itself 0.9 ms a
# weight.  In the step of the cell that has such a width (1856): 705.10 ->
# 625.18 ms, the expert layers 272.77 -> 192.74, the footprint 15.39 ->
# 15.27 GB (PERF.md section 6, PR 39).
_WIDTH_MULTIPLE = 256


def _padded_width(w_gate, w_up, w_down):
    """The experts' weights with zero columns (``w_gate``, ``w_up``) and
    zero rows (``w_down``) up to a width of ``_WIDTH_MULTIPLE``: a hidden
    unit of zeros gives silu(0) * 0 or relu(0)^2, times a row of zeros,
    so the result and every gradient are what they were.  Widths that are
    such a multiple come back as they are."""
    pad = -w_up.shape[-1] % _WIDTH_MULTIPLE
    if not pad:
        return w_gate, w_up, w_down
    cols, rows = ((0, 0), (0, 0), (0, pad)), ((0, 0), (0, pad), (0, 0))
    return (None if w_gate is None else jnp.pad(w_gate, cols),
            jnp.pad(w_up, cols), jnp.pad(w_down, rows))


@jax.named_scope(scopes.MOE_ROUTER)
def _count_load(sizes, small):
    """What this call's router did, for whoever reads the step's device
    counters (scopes.py).  ``sizes`` [chunks, held]: the assignments each
    held expert got from each chunk, the grouped products' own group
    sizes, handed out of the loop over chunks as a second result: a value
    cannot leave a loop's body any other way, and a result of the loop is
    made where the loop runs (counted beside it from the ids, XLA put
    the count off to the step's end and held the ids of every layer until
    then: 136 MB in the Keye cell)."""
    load = jnp.sum(sizes, 0)
    assigned = jnp.sum(sizes, 1)
    # a vector over more experts or chunks than a counter may hold (256)
    # stays in: its sum and its largest, below, are emitted all the same
    for name, vector in ((scopes.MOE_EXPERT_LOAD, load),
                         (scopes.MOE_CHUNK_ASSIGNMENTS, assigned)):
        if vector.size * 4 <= device_counters.MAX_VALUE_BYTES:
            device_counters.device_counter(name, vector)
    device_counters.device_counter(scopes.MOE_FULLEST_EXPERT_LOAD,
                                   jnp.max(load))
    # ``_by_load``'s predicate; without a small buffer every chunk takes
    # the full one
    device_counters.device_counter(
        scopes.MOE_FULL_BUFFER_CHUNKS,
        jnp.sum(assigned > small, dtype=jnp.int32) if small is not None
        else jnp.int32(sizes.shape[0]))


@jax.named_scope(scopes.MOE)
def moe_forward(x32, router_w, w_gate, w_up, w_down, *, top_k, first,
                norm_topk_prob=True, scoring="softmax", router_bias=None,
                scaling=1.0, shared=None, train_router=True,
                gate_epsilon=None):
    """x32 [..., H], the float32 normed stream -> the held experts' part
    of the layer's result, float32, same shape.  The experts take x in
    the weights' type; ``w_gate`` None: experts of the form without a
    gate.  ``scoring``, ``router_bias``, ``scaling`` and ``gate_epsilon``
    are ``moe_route``'s; ``shared`` the (gate, up, down) weights of a shared
    expert of the same form (its gate None too), whose result over every
    token is added.  ``train_router``
    False: no gradient reaches the router's weight or, through the
    router, the stream (the header says when)."""
    held, total = w_up.shape[0], router_w.shape[1]
    if not 0 <= first <= total - held:
        raise ValueError(f"experts {first}..{first + held - 1} are not "
                         f"among the router's {total}")
    # trace time, as pallas.selected.*: what the last layer traced holds,
    # and how many layers took the grouped product through ragged_dot
    monitor.stat_set("moe.experts_held", held)
    monitor.stat_set("moe.experts_total", total)
    monitor.stat_set("moe.top_k", top_k)
    monitor.stat_add("moe.ragged_dot_path")
    if scoring == "sigmoid":
        monitor.stat_add("moe.scoring_sigmoid")
    if shared is not None:
        monitor.stat_add("moe.shared_experts")
    if w_gate is None:
        monitor.stat_add("moe.gateless_experts")
    shape = x32.shape
    H = shape[-1]
    flat = x32.reshape(-1, H)
    N = flat.shape[0]
    chunk = shape[-2] if x32.ndim > 2 else N      # one sequence of the batch
    gates, ids = moe_route(flat, router_w, top_k, norm_topk_prob, scoring,
                           router_bias, scaling, gate_epsilon)
    if not train_router:
        gates = jax.lax.stop_gradient(gates)
    local = ids - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    x = flat.astype(w_up.dtype)
    small = _small_buffer(chunk, top_k, held, total)
    # what a chunk's gathers walk, beside moe.experts_held: with the device
    # counters below a reader turns counts into rows gathered
    monitor.stat_set("moe.small_buffer_rows", small or chunk * top_k)
    monitor.stat_set("moe.full_buffer_rows", chunk * top_k)
    # rows of H that a pass from rows back to tokens walks a chunk: a row
    # a slot in XLA's form; ``_experts`` says where a small buffer's are
    # walked in token order instead
    monitor.stat_set("moe.token_major_rows", chunk * top_k)
    counting = device_counters.collecting()
    experts = _padded_width(w_gate, w_up, w_down)
    out = jax.lax.map(
        lambda c: _chunk(small, *c, *experts, counting),
        (x.reshape(N // chunk, chunk, H),
         gates.reshape(N // chunk, chunk, top_k),
         local.reshape(N // chunk, chunk, top_k)))
    if counting:
        out, sizes = out
        _count_load(sizes, small)
    if shared is not None:
        out = out.reshape(N, H) + _shared_expert(x, *shared)
    return out.reshape(shape)
