"""The comparison that decides ``correct`` for a training cell.

The reference follows the timed step object through its first steps on
the same seeded weights and the same batches, in float32 at matmul
precision "highest", in blocks of rows so that it fits.  Compared, each
against a limit of its own from the cell's file:

  loss_gap            |program - reference| / reference, worst step
  grad_norm_gap       worst leaf: gap between the two norms of the first
                      gradient as the optimizer gets it (after the clip),
                      against the reference's norm of that leaf or of the
                      median leaf, whichever is larger
  update_norm_gap     the same for the parameters' change over the steps
  grad_diff           norm of the difference of the two first gradients
                      over a fixed sample of every leaf, against the
                      reference's norm over that sample

The control is the same reference computed one precision lower
(``precision="fp8"`` for a bfloat16 cell: matmul operands in e4m3, their
gradients in e5m2; ``"bfloat16"`` for a float32 one: weights, activations
and optimizer state all in bfloat16) and put in the program's place.
"""
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 1 << 16          # elements of each leaf kept for grad_diff
OFFLOAD_OVER = 1 << 30    # bytes of float32 parameters
ZERO_GRADIENT = 1e-3      # of the median leaf's norm: "zero by the maths"


def _round_fp8(x, dtype, top):
    """Round to an 8-bit float type under a per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(x.dtype) * s


@jax.custom_vjp
def _fp8(x):
    """The fp8 training recipe (Micikevicius et al. 2022) on one matmul
    operand: the matmul sees it rounded to e4m3, and the gradient that
    comes back for it is rounded to e5m2."""
    return _round_fp8(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_round_fp8(g, jnp.float8_e5m2, 57344.0),))


def key_of(leaf, block=None):
    """The name one compared leaf goes by: a block's slice of a stacked
    ``layers.*`` leaf is ``layers.q.w[3]``."""
    return leaf if block is None else f"{leaf}[{block}]"


def take(tree, key):
    """``tree[key]``, where ``key`` may name a block's slice."""
    if key.endswith("]"):
        leaf, _, block = key[:-1].rpartition("[")
        return tree[leaf][int(block)]
    return tree[key]


def _stacked(name):
    return name.startswith("layers.") and not name.endswith("]")


def expanded_keys(tree):
    """Every compared leaf of a reference-shaped tree (arrays or
    ``(shape, base)`` pairs), the stacked ones block by block."""
    out = []
    for n, a in tree.items():
        shape = a[0] if isinstance(a, tuple) else a.shape
        out += ([key_of(n, i) for i in range(shape[0])] if _stacked(n)
                else [n])
    return out


def _per_leaf(tree, fn):
    """``fn`` of every compared leaf as a [rows, elements] float32 array:
    a stacked leaf has a row per block, any other leaf one row."""
    out = {}
    for n, a in tree.items():
        a = a.astype(jnp.float32)
        rows = fn(a.reshape(a.shape[0], -1) if _stacked(n)
                  else a.reshape(1, -1))
        for i in range(rows.shape[0]):
            out[key_of(n, i) if _stacked(n) else n] = rows[i]
    return out


def _norms(tree):
    return _per_leaf(tree, lambda a: jnp.sqrt(jnp.sum(jnp.square(a), 1)))


def _samples(tree):
    return _per_leaf(tree, lambda a: a[:, :SAMPLE])


def clip_scale(grads, clip):
    """Global-norm clip (Pascanu et al. 2013): scale so that the joint
    norm is at most ``clip``."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in grads.values()))
    return clip / jnp.maximum(gn, clip)


def adam_update(p, g, m, v, t, opt):
    """Adam (Kingma & Ba 2015) with decoupled decay (Loshchilov & Hutter
    2019) on every leaf where ``weight_decay`` is set."""
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    p = p * (1 - opt["lr"] * opt.get("weight_decay", 0.0))
    return p - opt["lr"] * mhat / (jnp.sqrt(vhat) + opt["eps"]), m, v


def follow(ref, cfg, variant, theta0_fn, batches, opt, block_rows,
           precision="float32"):
    """Train the reference through ``batches`` (one step each) from
    ``theta0_fn()``.  Returns losses, the first gradient's norms and
    samples (after the clip), and the norms of the parameters' change."""
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    qz = _fp8 if precision == "fp8" else (lambda a: a)

    def block_grad(params, acc, ids, labels, w):
        l, g = jax.value_and_grad(ref.loss)(
            params, ids, labels, cfg, variant, qz)
        return l, jax.tree.map(lambda a, b: a + w * b.astype(a.dtype),
                               acc, g)

    block_grad = jax.jit(block_grad, donate_argnums=(1,))

    def finish(grads):
        if opt.get("clip_global_norm"):
            s = clip_scale(grads, opt["clip_global_norm"])
            grads = {n: g * s.astype(g.dtype) for n, g in grads.items()}
        return grads, _norms(grads), _samples(grads)

    finish = jax.jit(finish, donate_argnums=(0,))
    update = jax.jit(lambda p, g, m, v, t: adam_update(p, g, m, v, t, opt),
                     donate_argnums=(1, 2, 3))

    out = {"losses": [], "step_seconds": []}
    with jax.default_matmul_precision("highest"):
        params = {n: a.astype(dt) for n, a in theta0_fn().items()}
        # Adam's moments wait on the host between the steps of a model
        # whose float32 reference would not fit the chip otherwise
        offload = sum(a.nbytes for a in params.values()) > OFFLOAD_OVER
        m, v = {}, {}
        for t, (ids, labels) in enumerate(batches, start=1):
            t_step = time.perf_counter()
            rows = ids.shape[0]
            if rows % block_rows:
                raise ValueError(f"{rows} rows do not split into blocks "
                                 f"of {block_rows}")
            nb = rows // block_rows
            acc = {n: jnp.zeros_like(a) for n, a in params.items()}
            loss = 0.0
            for b in range(nb):
                sl = slice(b * block_rows, (b + 1) * block_rows)
                l, acc = block_grad(params, acc, jnp.asarray(ids[sl]),
                                    jnp.asarray(labels[sl]), 1.0 / nb)
                loss = loss + l.astype(jnp.float32) / nb
            out["losses"].append(float(loss))
            grads, norms, samples = finish(acc)
            del acc
            if t == 1:
                out["grad_norms"] = {n: float(x) for n, x in norms.items()}
                out["grad_samples"] = {n: np.asarray(x)
                                       for n, x in samples.items()}
            last = t == len(batches)
            for n in sorted(params):
                if t == 1:
                    m_n = jnp.zeros_like(params[n])
                    v_n = jnp.zeros_like(params[n])
                else:
                    m_n, v_n = jnp.asarray(m.pop(n)), jnp.asarray(v.pop(n))
                params[n], m_n, v_n = update(
                    params[n], grads.pop(n), m_n, v_n, jnp.float32(t))
                if not last:
                    m[n] = np.asarray(m_n) if offload else m_n
                    v[n] = np.asarray(v_n) if offload else v_n
            jax.block_until_ready(params)
            out["step_seconds"].append(
                round(time.perf_counter() - t_step, 3))
        del m, v, grads
        out["update_norms"] = update_norms(params, theta0_fn)
    return out


def update_norms(params, theta0_fn):
    """Per-leaf norm of ``params - theta0``, theta0 made again from the
    seed so that no second copy of it is held through the steps.
    ``params`` is keyed as the reference is (stacked blocks) or leaf by
    leaf (``key_of``), as a runner hands the program's."""
    theta0 = theta0_fn()
    f = jax.jit(lambda p, q: _norms(
        {k: p[k].astype(jnp.float32) - take(q, k) for k in p}))
    return {n: float(x) for n, x in f(params, theta0).items()}


def first_gradient(moments, opt):
    """The first gradient as the optimizer got it, from Adam's first
    moment after one step: m1 = (1 - beta1) * g.  Norms and samples."""
    f = jax.jit(lambda ms: (
        _norms({n: a.astype(jnp.float32) / (1 - opt["beta1"])
                for n, a in ms.items()}),
        _samples({n: a.astype(jnp.float32) / (1 - opt["beta1"])
                  for n, a in ms.items()})))
    norms, samples = f(moments)
    return ({n: float(x) for n, x in norms.items()},
            {n: np.asarray(x) for n, x in samples.items()})


def _worst_norm_gap(got, want, leaves=None):
    floor = statistics.median(want.values())
    gaps = {n: abs(got[n] - want[n]) / max(want[n], floor)
            for n in (leaves or want)}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def compare(got, want):
    """The numbers compared, name -> (value, where).  ``got`` and ``want``
    are two ``follow``-shaped results; ``got`` is the program's (or the
    control's), ``want`` the float32 reference's."""
    steps = range(len(want["losses"]))
    gaps = [abs(got["losses"][i] - want["losses"][i]) /
            abs(want["losses"][i]) for i in steps]
    worst = max(steps, key=lambda i: gaps[i])
    num = sum(float(np.sum(np.square(got["grad_samples"][n] - w)))
              for n, w in want["grad_samples"].items())
    den = sum(float(np.sum(np.square(w)))
              for w in want["grad_samples"].values())
    # Adam turns the rounding noise of a gradient that is zero by the
    # mathematics (a key bias under softmax) into full-size steps of
    # random sign: such leaves say nothing about the update
    floor = ZERO_GRADIENT * statistics.median(want["grad_norms"].values())
    live = [n for n, g in want["grad_norms"].items() if g > floor]
    out = {"loss_gap": (gaps[worst], f"step {worst + 1}"),
           "grad_norm_gap": _worst_norm_gap(got["grad_norms"],
                                            want["grad_norms"]),
           "update_norm_gap": _worst_norm_gap(got["update_norms"],
                                              want["update_norms"], live),
           "grad_diff": ((num / den) ** 0.5, "all leaves' samples")}
    return out


def verdict(numbers, limits, log=print):
    """Print each number beside its limit; True if all are within."""
    ok = True
    for name, (value, where) in numbers.items():
        limit = limits.get(name)
        if limit is None:
            # a cell without its limits has not been shown to be correct
            log(f"[check] {name} = {value:.6g} ({where}); NO LIMIT SET")
            ok = False
            continue
        good = np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        log(f"[check] {name} = {value:.6g} ({where}); limit {limit:g}: "
            f"{'within' if good else 'OVER'}")
    return ok
