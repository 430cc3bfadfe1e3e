"""dy2static AST conversion tests (reference analog:
dygraph_to_static/test_ifelse.py): Python `if` on tensor predicates is
rewritten to cond inside to_static; eager semantics are untouched."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, nn


def test_if_else_assignment_pattern_converts():
    @jit.to_static
    def f(x):
        if x.sum() > 0:
            y = x * 2
        else:
            y = x - 1
        return y + 10

    a = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
    b = paddle.to_tensor(np.array([-1.0, -2.0], np.float32))
    np.testing.assert_allclose(f(a).numpy(), [12.0, 14.0])
    np.testing.assert_allclose(f(b).numpy(), [8.0, 7.0])  # same compiled fn


def test_early_return_pattern_converts():
    @jit.to_static
    def relu_ish(x):
        if x.sum() > 0:
            return x
        return -x

    a = paddle.to_tensor(np.array([3.0], np.float32))
    b = paddle.to_tensor(np.array([-3.0], np.float32))
    assert float(relu_ish(a)) == 3.0
    assert float(relu_ish(b)) == 3.0


def test_if_return_else_return_converts():
    @jit.to_static
    def pick(x):
        if x.mean() > 0:
            return x * 10
        else:
            return x * 100

    assert float(pick(paddle.to_tensor(np.array([1.0], np.float32)))) == 10.0
    assert float(pick(paddle.to_tensor(np.array([-1.0], np.float32)))) == -100.0


def test_multi_assign_both_branches():
    @jit.to_static
    def f(x):
        if x.sum() > 0:
            a = x + 1
            b = x * 2
        else:
            a = x - 1
            b = x / 2
        return a + b

    v = paddle.to_tensor(np.array([2.0], np.float32))
    np.testing.assert_allclose(float(f(v)), 7.0)
    v2 = paddle.to_tensor(np.array([-2.0], np.float32))
    np.testing.assert_allclose(float(f(v2)), -4.0)


def test_static_if_on_python_value_untouched():
    @jit.to_static
    def f(x, flag=True):
        if flag:                # plain Python bool: normal trace-time if
            return x * 2
        return x

    assert float(f(paddle.to_tensor(np.array([2.0], np.float32)))) == 4.0


def test_single_arm_if_converts():
    """`if c: x = x * 2` with x pre-bound synthesizes an identity else
    (round-5 extension; this used to bail)."""
    @jit.to_static
    def f(x):
        if x.sum() > 0:
            x = x * 2
        return x

    assert float(np.asarray(f(paddle.to_tensor(
        np.array([3.0], np.float32))).data)[0]) == 6.0
    assert float(np.asarray(f(paddle.to_tensor(
        np.array([-3.0], np.float32))).data)[0]) == -3.0


def test_unconvertible_pattern_still_fails_loudly():
    @jit.to_static
    def f(x):
        if x.sum() > 0:
            y = x * 2      # branches assign DIFFERENT names: no convert
        else:
            z = x
        return x

    with pytest.raises(TypeError, match="paddle.cond"):
        f(paddle.ones([2]))


def test_converted_if_differentiable():
    @jit.to_static
    def f(x):
        if x.sum() > 0:
            y = (x * x).sum()
        else:
            y = x.sum()
        return y

    x = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                         stop_gradient=False)
    f(x).backward()
    np.testing.assert_allclose(x.grad.numpy(), [2.0, 4.0])


def test_layer_forward_with_tensor_if():
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)

        @jit.to_static
        def forward(self, x):
            h = self.fc(x)
            if h.sum() > 1e9:   # never true, but must trace both
                out = h * 0
            else:
                out = h + 1
            return out

    net = Net()
    x = paddle.randn([2, 4])
    expect = (net.fc(x) + 1).numpy()
    np.testing.assert_allclose(net(x).numpy(), expect, rtol=1e-6)


def test_branch_self_assignment_converts():
    """`x = x + 1` inside a branch reads its own target: converted via
    default-argument snapshots (round-4 upgrade; was a documented
    non-convertible case before)."""
    @jit.to_static
    def g(x, flag=True):
        if flag:
            x = x + 1
        else:
            x = x - 1
        return x

    assert float(g(paddle.to_tensor(np.array([1.0], np.float32)))) == 2.0

    @jit.to_static
    def h(x):
        if x.sum() > 0:
            x = x * 2
        else:
            x = x - 1
        return x

    np.testing.assert_allclose(h(paddle.ones([2])).numpy(), [2.0, 2.0])
    np.testing.assert_allclose(
        h(paddle.to_tensor(np.array([-1.0, -1.0], np.float32))).numpy(),
        [-2.0, -2.0])


def test_chained_assign_after_define_converts():
    @jit.to_static
    def f(x):
        if x.sum() > 0:
            a = x * 2
            b = a + 1      # reads `a` AFTER assigning it: fine
        else:
            a = x - 1
            b = a * 3
        return b

    np.testing.assert_allclose(
        float(f(paddle.to_tensor(np.array([1.0], np.float32)))), 3.0)
    np.testing.assert_allclose(
        float(f(paddle.to_tensor(np.array([-1.0], np.float32)))), -6.0)


# -- loop conversion (reference: loop_transformer.py, test_loop.py) -------

def test_while_loop_converts_under_to_static():
    @jit.to_static
    def f(x):
        s = x * 0
        while s.sum() < 10:
            s = s + x
        return s

    x = paddle.to_tensor(np.array([1.0, 1.0], np.float32))
    out = f(x).numpy()
    np.testing.assert_allclose(out, [5.0, 5.0])  # 5 iters * 2 elements
    # compiled: second call reuses the traced while_loop
    out2 = f(paddle.to_tensor(np.array([2.0, 2.0], np.float32))).numpy()
    np.testing.assert_allclose(out2, [6.0, 6.0])


def test_while_eager_semantics_unchanged():
    from paddle_tpu.jit.dy2static import convert_control_flow

    def f(n):
        s = 0
        while s < n:
            s = s + 3
        return s

    g = convert_control_flow(f)
    assert g is not f          # converted
    assert g(10) == f(10) == 12


def test_for_range_converts():
    @jit.to_static
    def f(x, n):
        acc = x * 0
        for i in range(n):
            acc = acc + x * (i + 1)
        return acc

    x = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
    n = paddle.to_tensor(np.int32(4))
    # range over a TENSOR bound — impossible in plain Python, works
    # converted (loop_transformer semantics)
    out = f(x, n).numpy()
    np.testing.assert_allclose(out, [10.0, 20.0])


def test_loop_with_leading_break():
    @jit.to_static
    def f(x):
        s = x * 0
        k = x.sum() * 0
        while k < 100:
            if s.sum() > 6:
                break
            s = s + x
            k = k + 1
        return s

    x = paddle.to_tensor(np.array([1.0, 1.0], np.float32))
    out = f(x).numpy()
    # breaks once sum > 6 -> s = [4, 4] (sum 8)
    np.testing.assert_allclose(out, [4.0, 4.0])


def test_loop_with_tail_break():
    from paddle_tpu.jit.dy2static import convert_control_flow

    def f(lim):
        s = 0
        while True:
            s = s + 2
            if s >= lim:
                break
        return s

    g = convert_control_flow(f)
    assert g is not f
    assert g(7) == f(7) == 8


def test_loop_with_continue():
    from paddle_tpu.jit.dy2static import convert_control_flow

    def f(n):
        s = 0
        i = 0
        while i < n:
            i = i + 1
            if i % 2 == 0:
                continue
            s = s + i
        return s

    # leading-continue only converts when the if is FIRST; this one is
    # mid-body -> must stay unconverted but still correct in Python
    g = convert_control_flow(f)
    assert g(6) == f(6) == 9

    def f2(n):
        s = 0
        i = 0
        while i < n:
            if _is_even(i):
                i = i + 1
                continue
            s = s + i
            i = i + 1
        return s

    # (leading continue pattern is exercised via tensors below)


def _is_even(i):
    return i % 2 == 0


def test_nested_if_inside_loop_converts():
    @jit.to_static
    def f(x):
        s = x * 0
        for i in range(4):
            if s.sum() > 2:
                s = s + x * 2
            else:
                s = s + x
        return s

    x = paddle.to_tensor(np.array([1.0, 1.0], np.float32))
    # iters: s=[1,1](sum0->cond False), [2,2](sum2 False), [4,4](sum4 True), [6,6]
    np.testing.assert_allclose(f(x).numpy(), [6.0, 6.0])


def test_unconvertible_loop_left_untouched():
    from paddle_tpu.jit.dy2static import convert_control_flow

    def f(xs):
        out = []
        for x in xs:               # iterating a list: not convertible
            out.append(x * 2)
        return out

    g = convert_control_flow(f)
    assert g([1, 2]) == [2, 4]


# -- r4 review regressions ------------------------------------------------

def test_break_predicate_reads_body_assigned_name():
    """r4 review: a break predicate reading a body-assigned name that is
    not otherwise live must still be carried (was: stale snapshot, loop
    never broke)."""
    from paddle_tpu.jit.dy2static import convert_control_flow

    def f(x):
        s = 0
        k = 0
        t = 0
        while k < 100:
            if t > 6:
                break
            t = s + 1
            s = s + x
            k = k + 1
        return s

    g = convert_control_flow(f)
    assert g(1) == f(1) == 7


def test_unbound_prebind_name_not_converted():
    """r4 review: `if flag: y = y + 1 else: y = 0` with y unbound before
    the if must NOT convert (the default-arg snapshot would raise where
    plain Python, branch untaken, would not)."""
    from paddle_tpu.jit.dy2static import convert_control_flow

    def f(flag):
        if flag:
            y = y_missing_on_purpose + 1  # noqa: F821
        else:
            y = 0
        return y

    g = convert_control_flow(f)
    assert g(False) == 0          # python semantics preserved

    def h(flag):
        if flag:
            z = z + 1  # noqa: F821 — z unbound: must not prebind
        else:
            z = 0
        return z

    k = convert_control_flow(h)
    assert k(False) == 0


def test_tensor_if_inside_tensor_while_converts():
    """r4 review: the if-converter's generated closures contain Return;
    the loop converter must not reject them."""
    @jit.to_static
    def f(x):
        s = x * 0
        while s.sum() < 6:
            if s.sum() > 2:
                s = s + x * 2
            else:
                s = s + x
        return s

    x = paddle.to_tensor(np.array([1.0, 1.0], np.float32))
    # s: [1,1](2) -> [2,2](4>2) ... iter1 sum0->else [1,1]; iter2 sum2->else [2,2]; iter3 sum4>2 -> [4,4]; sum8 stop
    np.testing.assert_allclose(f(x).numpy(), [4.0, 4.0])


# ---- round-5 breadth (VERDICT r4 #5): break/continue anywhere, early
# return in loops, converted nested calls --------------------------------

def _eager_vs_static(fn, *inputs):
    """Run eager and to_static on the same inputs; outputs must match."""
    eager = fn(*inputs)
    static = jit.to_static(fn)(*inputs)
    np.testing.assert_allclose(np.asarray(static.data),
                               np.asarray(eager.data), rtol=1e-6)
    return static


def test_mid_body_break():
    def f(x):
        s = paddle.zeros([2])
        i = 0
        while i < 10:
            s = s + x
            if s.sum() > 6:
                break
            s = s * 1.5
            i = i + 1
        return s

    x = paddle.to_tensor(np.array([1.0, 1.0], np.float32))
    _eager_vs_static(f, x)


def test_mid_body_continue_in_for():
    def f(x):
        s = x * 0
        for i in range(6):
            s = s + x
            if s.sum() > 4:
                continue
            s = s + 100 * x  # skipped once the running sum passes 4
        return s

    x = paddle.to_tensor(np.array([1.0], np.float32))
    _eager_vs_static(f, x)


def test_multiple_exits_mixed():
    def f(x):
        s = x * 0
        for i in range(8):
            if s.sum() > 20:
                break
            s = s + x
            if s.sum() < 2:
                continue
            s = s * 2
        return s

    for v in (0.5, 1.0, 3.0):
        x = paddle.to_tensor(np.array([v], np.float32))
        _eager_vs_static(f, x)


def test_break_with_payload_assignment():
    def f(x):
        s = x * 0
        flag = paddle.zeros([1])
        for i in range(5):
            s = s + x
            if s.sum() > 2:
                flag = flag + 1
                break
        return s + flag * 10

    x = paddle.to_tensor(np.array([1.0], np.float32))
    _eager_vs_static(f, x)


def test_early_return_inside_loop():
    def f(x):
        s = x * 0
        for i in range(10):
            s = s + x
            if s.sum() > 3:
                return s * 100
        return s

    # one input that trips the early return, one that does not
    hit = paddle.to_tensor(np.array([1.0], np.float32))
    miss = paddle.to_tensor(np.array([0.1], np.float32))
    _eager_vs_static(f, hit)
    _eager_vs_static(f, miss)


def test_early_return_inside_while():
    def f(x):
        s = x * 0
        i = 0
        while i < 20:
            s = s + x
            if s.sum() > 5:
                return -s
            i = i + 1
        return s

    _eager_vs_static(f, paddle.to_tensor(np.array([2.0], np.float32)))
    _eager_vs_static(f, paddle.to_tensor(np.array([0.1], np.float32)))


def _helper_double_or_neg(v):
    # module-level helper with a tensor if: must be converted when
    # called from a to_static fn (call_transformer parity)
    if v.sum() > 0:
        return v * 2
    return -v


def test_nested_call_converts():
    def f(x):
        y = _helper_double_or_neg(x)
        return y + 1

    pos = paddle.to_tensor(np.array([2.0], np.float32))
    neg = paddle.to_tensor(np.array([-2.0], np.float32))
    _eager_vs_static(f, pos)
    _eager_vs_static(f, neg)


def test_nested_call_inside_loop_converts():
    def f(x):
        s = x * 0
        for i in range(4):
            s = _helper_double_or_neg(s + x)
        return s

    _eager_vs_static(f, paddle.to_tensor(np.array([1.0], np.float32)))
    _eager_vs_static(f, paddle.to_tensor(np.array([-1.0], np.float32)))


def test_nested_call_shadowed_name_stays_loud():
    """A call through a local alias cannot be resolved at conversion
    time: the callee runs UNCONVERTED, and its tensor-if raises the
    loud trace error instead of silently mistracing (design rule)."""
    def f(x):
        _local = _helper_double_or_neg
        return _local(x)

    x = paddle.to_tensor(np.array([1.5], np.float32))
    assert float(f(x).data[0]) == 3.0  # eager path unaffected
    with pytest.raises(TypeError, match="paddle.cond"):
        jit.to_static(f)(x)


def test_jst_call_passthrough():
    from paddle_tpu.jit.dy2static import _jst_call
    assert _jst_call(len) is len            # builtin
    assert _jst_call(range) is range        # type
    assert _jst_call(np.sum) is np.sum      # library fn
    obj = object()
    assert _jst_call(obj) is obj            # arbitrary value
    # user helper converts and is memoized
    c1 = _jst_call(_helper_double_or_neg)
    c2 = _jst_call(_helper_double_or_neg)
    assert c1 is c2 and c1 is not _helper_double_or_neg


def test_traced_loop_break_lowers_to_while():
    """The converted loop must lower to ONE lax.while under to_static:
    the body traces once, it does not run per iteration or unroll."""
    calls = [0]

    def probe(v):
        calls[0] += 1  # python side effect: fires once per TRACE
        return v

    def f(x):
        s = x * 0
        for i in range(100):
            s = s + probe(x)
            if s.sum() > 10:
                break
        return s

    g = jit.to_static(f)
    out = g(paddle.to_tensor(np.array([3.0], np.float32)))
    assert float(np.asarray(out.data)[0]) == 12.0  # 3,6,9,12 -> break
    # bounded tracing (lax.while traces the body twice for the carry
    # fixed-point) — NOT 4 eager iterations, not 100 unrolled
    assert calls[0] <= 2, calls[0]


def test_return_of_body_temp_bails_loudly():
    """Early return of a body-local temp can't init the carry pre-loop:
    the loop must stay unconverted and raise the LOUD trace error, never
    a NameError from generated code."""
    def f(x):
        s = x * 0
        for i in range(5):
            t = x * 2.0
            if t.sum() > 3:
                return t
            s = s + t
        return s

    x = paddle.to_tensor(np.array([2.0], np.float32))
    assert float(f(x).data[0]) == 4.0  # eager: t=4 > 3 on iter 0
    with pytest.raises(TypeError, match="paddle.cond"):
        jit.to_static(f)(x)


def test_return_reading_loop_index_bails_loudly():
    def f(x):
        s = x * 0
        for i in range(5):
            s = s + x
            if s.sum() > 2:
                return s * i
        return s

    x = paddle.to_tensor(np.array([1.0], np.float32))
    with pytest.raises(TypeError, match="paddle.cond"):
        jit.to_static(f)(x)


def test_payload_name_without_preloop_binding_bails_loudly():
    def f(x):
        s = x * 0
        for i in range(5):
            s = s + x
            if s.sum() > 2:
                msg = s * 0
                break
        return s + msg  # noqa: F821 - bound only when the break fires

    x = paddle.to_tensor(np.array([1.0], np.float32))
    assert float(f(x).data[0]) == 3.0  # eager: break fires, msg bound
    with pytest.raises(TypeError, match="paddle.cond"):
        jit.to_static(f)(x)


def test_return_in_loop_with_nontrailing_return_bails_loudly():
    def f(x):
        s = x * 0
        for i in range(10):
            s = s + x
            if s.sum() > 3:
                return s * 100
        y = s * 2
        return y

    x = paddle.to_tensor(np.array([1.0], np.float32))
    assert float(f(x).data[0]) == 400.0
    with pytest.raises(TypeError, match="paddle.cond"):
        jit.to_static(f)(x)


# ---- print / cast / assert transformers (reference: print_transformer,
# cast_transformer, assert_transformer) ----------------------------------

def test_print_inside_traced_fn(capfd):
    @jit.to_static
    def f(x):
        y = x * 2
        print("value:", y)
        return y + 1

    out = f(paddle.to_tensor(np.array([3.0], np.float32)))
    assert float(out.data[0]) == 7.0
    # jax.debug.print emits the RUNTIME value (not a tracer repr)
    captured = capfd.readouterr()
    text = captured.out + captured.err
    assert "6." in text and "Tracer" not in text


def test_print_in_converted_loop(capfd):
    @jit.to_static
    def f(x):
        s = x * 0
        for i in range(3):
            s = s + x
            print(s)
        return s

    out = f(paddle.to_tensor(np.array([1.0], np.float32)))
    assert float(out.data[0]) == 3.0
    cap = capfd.readouterr()
    text = cap.out + cap.err
    # one print per ITERATION at runtime (3 values), not one per trace
    assert text.count("[") >= 3, text


def test_cast_on_traced_tensor():
    @jit.to_static
    def f(x):
        i = int(x)          # -> astype int64 under trace
        fl = float(i)       # -> astype float32
        return fl * 2

    out = f(paddle.to_tensor(np.array([3.7], np.float32)))
    assert float(out.data[0]) == 6.0  # trunc to 3 then *2
    # eager parity: builtin semantics preserved (python scalar)
    assert int(np.asarray(paddle.to_tensor(
        np.array([3.7], np.float32)).data)[0] * 0 + 3.7) == 3


def test_cast_concrete_passthrough():
    @jit.to_static
    def f(x, k):
        n = int(k)          # concrete python value -> builtin int
        return x * n

    out = f(paddle.to_tensor(np.array([2.0], np.float32)), 3.9)
    assert float(out.data[0]) == 6.0


def test_assert_traced_checks_at_runtime():
    @jit.to_static
    def f(x):
        assert x.sum() > 0, "must be positive"
        return x * 2

    ok = f(paddle.to_tensor(np.array([1.0], np.float32)))
    assert float(ok.data[0]) == 2.0
    with pytest.raises(Exception, match="must be positive"):
        out = f(paddle.to_tensor(np.array([-1.0], np.float32)))
        np.asarray(out.data)  # force execution on async backends


def test_assert_concrete_keeps_python_semantics():
    def g(flag):
        assert flag, "nope"
        return 1

    conv = jit.to_static(g)
    assert conv(True) == 1
    with pytest.raises(AssertionError, match="nope"):
        conv(False)


def test_shadowed_builtin_names_untouched():
    """A param/local/module binding named int/float/bool/print must NOT
    be hijacked by the builtin transformer (review-confirmed repro)."""
    def h(x, int):
        if x.sum() > 0:  # force conversion
            y = x
        else:
            y = -x
        return y * int(x)

    out = jit.to_static(h)(
        paddle.to_tensor(np.array([2.0], np.float32)), lambda v: 10.0)
    assert float(np.asarray(out.data)[0]) == 20.0


def test_bt_only_conversion_keeps_live_closures():
    """A function whose only convertible construct is a print must not
    be recompiled when it has a closure — recompiling snapshots cells
    and freezes live nonlocals (review-confirmed repro).  Checked at
    the convert_control_flow level: under to_static's jit cache,
    closures are trace-time constants anyway."""
    from paddle_tpu.jit.dy2static import convert_control_flow

    def outer():
        factor = [2.0]
        state = {"factor": 2.0}

        def set_factor(v):
            state["factor"] = v
            nonlocal_set(v)

        def nonlocal_set(v):
            nonlocal real_factor
            real_factor = v

        real_factor = 2.0

        def inner(x):
            print("factor is", real_factor)
            return x * real_factor

        return inner, set_factor

    inner, set_factor = outer()
    conv = convert_control_flow(inner)
    assert conv is inner  # closure-bearing, bt-only: left untouched
    assert conv(1.0) == 2.0
    set_factor(5.0)
    assert conv(1.0) == 5.0  # closure stays LIVE


def test_assert_msg_lazy():
    calls = [0]

    def expensive():
        calls[0] += 1
        return "boom"

    @jit.to_static
    def f(x):
        assert x.sum() > 0, expensive()
        return x * 2

    f(paddle.to_tensor(np.array([1.0], np.float32)))
    assert calls[0] == 0  # passing assert never evaluates the message


def test_print_sep_honored_and_file_falls_back(capfd):
    @jit.to_static
    def f(x):
        print("v", x, sep="|")
        return x

    f(paddle.to_tensor(np.array([1.0], np.float32)))
    cap = capfd.readouterr()
    assert "v|" in (cap.out + cap.err)


def test_print_and_assert_run_as_host_callbacks(capfd):
    """A traced print shows the RUNTIME value on every call of the one
    compiled program, and a traced assert checks the runtime value —
    both through host callbacks, the only path there is."""
    import jax

    @jit.to_static
    def f(x):
        print("runtime value", x.sum())
        assert x.sum() > 0, "sum must be positive"
        return x * 2

    for v in (2.0, 3.0):
        out = f(paddle.to_tensor(np.array([v], np.float32)))
        assert float(np.asarray(out.data)[0]) == 2 * v
    jax.effects_barrier()
    cap = capfd.readouterr()
    assert "runtime value 2" in cap.out and "runtime value 3" in cap.out

    with pytest.raises(Exception, match="sum must be positive"):
        f(paddle.to_tensor(np.array([-1.0], np.float32)))
        jax.effects_barrier()


# ---- logical transformer (reference: logical_transformer.py) -----------

def test_logical_and_or_not_on_tensors():
    @jit.to_static
    def f(x):
        if (x.sum() > 0) and (x.max() < 10):
            y = x * 2
        else:
            y = x * 0
        if not (x.sum() > 100) or (x.min() < -50):
            y = y + 1
        return y

    out = f(paddle.to_tensor(np.array([2.0], np.float32)))
    assert float(np.asarray(out.data)[0]) == 5.0  # 2*2 + 1
    out2 = f(paddle.to_tensor(np.array([20.0], np.float32)))
    assert float(np.asarray(out2.data)[0]) == 1.0  # else branch, +1


def test_logical_short_circuit_preserved_eager():
    from paddle_tpu.jit.dy2static import convert_control_flow
    calls = []

    def right():
        calls.append(1)
        return "rhs"

    def f(flag):
        a = flag and right()
        b = flag or right()
        return a, b

    conv = convert_control_flow(f)
    a, b = conv(False)
    # `and` short-circuits (rhs NOT evaluated), returns the operand
    assert a is False and len(calls) == 1  # only the `or` ran rhs
    assert b == "rhs"
    calls.clear()
    a, b = conv(True)
    assert a == "rhs" and b is True and len(calls) == 1


def test_logical_in_while_test():
    @jit.to_static
    def f(x):
        s = x * 0
        i = 0
        while (i < 10) and (s.sum() < 5):
            s = s + x
            i = i + 1
        return s

    out = f(paddle.to_tensor(np.array([2.0], np.float32)))
    assert float(np.asarray(out.data)[0]) == 6.0  # 2,4,6 -> stop
