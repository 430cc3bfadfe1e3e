"""Device counters (``observability.device_counter``): what a compiled
``TrainStep`` counts while it runs, kept in its carry and read on demand
into ``utils.monitor``.  The expert layer's three counters are held to a
numpy recount of the step's own router ids, which leave the step the
same way (a test-only counter), so program and recount see one routing."""
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, observability, optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.observability import device_counters, scopes
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.parallel import recompute
from paddle_tpu.utils import monitor

H, FF, E, K, V = 32, 16, 16, 4, 50
HELD = range(2, 6)
ROWS, SEQ = 4, 16                 # ROWS * SEQ * K ids are 1 KiB exactly
IDS = "test.router_ids"


@pytest.fixture(autouse=True)
def _fresh_registry():
    """No earlier test's step left alive for the reader, and its names
    out of the registry."""
    gc.collect()
    observability.read_device_counters()
    monitor.stat_reset()
    yield
    monitor.stat_reset()


@pytest.fixture
def ids_out(monkeypatch):
    """Every ``moe_route`` of a step also emits its ids."""
    route = moe_ops.moe_route

    def emitting(*args, **kwargs):
        gates, ids = route(*args, **kwargs)
        observability.device_counter(IDS, ids.reshape(-1))
        return gates, ids

    monkeypatch.setattr(moe_ops, "moe_route", emitting)


class Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.norm = nn.RMSNorm(H)
        self.moe = nn.MoELayer(H, FF, E, K, held=HELD)

    def forward(self, x):
        return x + self.moe(self.norm(x))


class Net(nn.Layer):
    def __init__(self, blocks=2, ckpt=False):
        super().__init__()
        self.emb = nn.Embedding(V, H)
        self.blocks = nn.LayerList([Block() for _ in range(blocks)])
        self.head = nn.Linear(H, V)
        self.ckpt = ckpt

    def forward(self, ids):
        x = self.emb(ids)
        for b in self.blocks:
            x = recompute(b, x) if self.ckpt else b(x)
        return self.head(x)


def _loss(out, lab):
    return nn.functional.cross_entropy(out.reshape([-1, V]),
                                       lab.reshape([-1]))


def _batch(seed=0, rows=ROWS):
    ids = np.random.RandomState(seed).randint(0, V, (rows, SEQ))
    return jnp.asarray(ids, jnp.int32)


def _step(net=None, **kwargs):
    paddle.seed(7)
    net = net or Net()
    opt = optimizer.AdamW(learning_rate=1e-2, parameters=net.parameters())
    return TrainStep(net, _loss, opt, **kwargs)


def _last(step, name):
    return np.asarray(step.counter_carry()[name]["last"])


def _recount(ids, small):
    """ids [calls, chunks, chunk * K] -> the three counters by hand."""
    local = ids - HELD.start
    held = (local >= 0) & (local < len(HELD))
    load = np.stack([[np.sum(call == e) for e in HELD]
                     for call in ids])
    assigned = held.sum(-1)
    return load, assigned, (assigned > small).sum(-1)


def _check_against_recount(step, calls, rows=ROWS):
    small = moe_ops._small_buffer(SEQ, K, len(HELD), E)
    assert monitor.all_stats()["moe.small_buffer_rows"] == small
    assert monitor.all_stats()["moe.full_buffer_rows"] == SEQ * K
    ids = _last(step, IDS).reshape(calls, rows, SEQ * K)
    load, assigned, full = _recount(ids, small)
    np.testing.assert_array_equal(_last(step, scopes.MOE_EXPERT_LOAD), load)
    np.testing.assert_array_equal(
        _last(step, scopes.MOE_CHUNK_ASSIGNMENTS), assigned)
    np.testing.assert_array_equal(
        _last(step, scopes.MOE_FULL_BUFFER_CHUNKS), full)
    np.testing.assert_array_equal(
        _last(step, scopes.MOE_FULLEST_EXPERT_LOAD), load.max(-1))
    return load, assigned, full


@pytest.mark.parametrize("ckpt", [False, True], ids=["plain", "recompute"])
def test_moe_counters_equal_a_recount_of_the_router_ids(ids_out, ckpt):
    """Two expert layers: each counter is [2, ...], the calls in the
    model's order, a call's load by expert and by chunk (row)."""
    step = _step(Net(ckpt=ckpt))
    for seed in range(3):
        step(_batch(seed), _batch(seed))
        load, assigned, _ = _check_against_recount(step, calls=2)
    assert load.shape == (2, len(HELD)) and assigned.shape == (2, ROWS)
    # the two layers route differently: an order mixed up would show
    assert not np.array_equal(load[0], load[1])
    assert int(step.counter_carry()[scopes.MOE_EXPERT_LOAD]["steps"]) == 3


def test_a_chunk_over_the_small_buffer_is_counted(ids_out):
    """Rows that send every token to the held experts outgrow the small
    buffer; the rest stay under it."""
    net = Net(blocks=1)
    w = np.array(net.blocks[0].moe.router_weight.data)
    w[:, HELD.start:HELD.stop] += 1.0
    net.blocks[0].moe.router_weight.data = jnp.asarray(w)
    net.emb.weight.data = jnp.abs(net.emb.weight.data)
    step = _step(net)
    step(_batch(), _batch())
    _, assigned, full = _check_against_recount(step, calls=1)
    assert full[0] > 0 and assigned.max() > moe_ops._small_buffer(
        SEQ, K, len(HELD), E)


def test_micro_batches_are_summed(ids_out):
    """``accumulate_steps=2``: a step's count is the sum of its two
    micro-batches', each what a step over that half alone counts."""
    whole = _step(accumulate_steps=2)
    whole(_batch(), _batch())
    halves = []
    for part in (slice(0, 2), slice(2, 4)):
        half = _step()
        half(_batch()[part], _batch()[part])
        _check_against_recount(half, calls=2, rows=2)
        halves.append(half)
    for name in (scopes.MOE_EXPERT_LOAD, scopes.MOE_CHUNK_ASSIGNMENTS,
                 scopes.MOE_FULL_BUFFER_CHUNKS):
        np.testing.assert_array_equal(
            _last(whole, name), sum(_last(h, name) for h in halves))
    assert _last(whole, scopes.MOE_EXPERT_LOAD).sum() > 0


class Emitter(nn.Layer):
    """One output and one emission: what a JoyAI block is to recompute."""

    def __init__(self, emits):
        super().__init__()
        self.lin = nn.Linear(H, H)
        self.emits = emits

    def forward(self, x):
        y = self.lin(x)
        if self.emits:
            observability.device_counter("test.positive", jnp.sum(
                y.data > 0, dtype=jnp.int32))
            observability.device_counter("test.mean", jnp.mean(y.data))
        return y


class EmitterNet(nn.Layer):
    def __init__(self, emits=True):
        super().__init__()
        self.emb = nn.Embedding(V, H)
        self.a, self.b = Emitter(emits), Emitter(emits)
        self.head = nn.Linear(H, V)

    def forward(self, ids):
        return self.head(recompute(self.b, recompute(self.a, self.emb(ids))))


def test_recompute_carries_emissions_out_and_adds_no_barrier():
    """A segment with one output has no ``optimization_barrier`` of
    recompute's, with emissions as without (``jax.checkpoint`` lowers to
    some of its own); scalars stack to [calls], float32 allowed."""
    barriers = []
    for emits in (False, True):
        step = _step(EmitterNet(emits))
        step(_batch(), _batch())
        barriers.append(step._compiled[True].lower(*_step_args(
            step, _batch())).as_text().count("optimization_barrier"))
    assert barriers[0] == barriers[1]
    carry = step.counter_carry()
    assert carry["test.positive"]["last"].shape == (2,)
    assert carry["test.positive"]["last"].dtype == jnp.int32
    assert carry["test.mean"]["last"].dtype == jnp.float32
    assert 0 < int(carry["test.positive"]["last"][0]) < ROWS * SEQ * H


def _step_args(step, ids):
    return (step._param_arrays(), (), step._opt_state, step._scaler_state,
            step._lr_device, (ids,), (ids,))


def test_a_read_changes_nothing_the_step_computes():
    """Losses and parameters after 5 steps are bit-equal with a read
    after every step and with none."""
    runs = []
    for reads in (True, False):
        step = _step()
        losses = []
        for seed in range(5):
            losses.append(np.asarray(step(_batch(seed), _batch(seed)).data))
            if reads:
                step.device_counters()
        runs.append((losses, [np.asarray(p.data) for p in step._params]))
    for a, b in zip(runs[0][0], runs[1][0]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(runs[0][1], runs[1][1]):
        np.testing.assert_array_equal(a, b)


def test_the_first_calls_trace_is_not_the_steps_python():
    """The step is traced before its first call (to learn what it emits);
    ``train_step.python_ns`` leaves that out, as it left out the trace
    inside the first call before."""
    step = _step()
    t0 = time.perf_counter_ns()
    step(_batch(), _batch())
    first_call_ns = time.perf_counter_ns() - t0
    assert monitor.all_stats()["train_step.python_ns"] < first_call_ns / 4


def test_a_read_drains_the_carry_into_the_registry():
    """``total`` and ``steps`` move to the registry and are zeroed,
    ``last`` stays; a second read adds nothing; the registry goes on
    counting across reads."""
    step = _step()
    name = scopes.MOE_CHUNK_ASSIGNMENTS
    step(_batch(0), _batch(0))
    first = _last(step, name)
    step(_batch(1), _batch(1))
    second = _last(step, name)
    carry = step.counter_carry()[name]
    np.testing.assert_array_equal(np.asarray(carry["total"]), first + second)
    assert int(carry["steps"]) == 2

    view = observability.read_device_counters()
    carry = step.counter_carry()[name]
    assert int(carry["steps"]) == 0 and not np.asarray(carry["total"]).any()
    np.testing.assert_array_equal(np.asarray(carry["last"]), second)
    assert view[f"{name}.steps"] == 2
    assert view[f"{name}.total.1.3"] == first[1, 3] + second[1, 3]
    assert view[f"{name}.last.1.3"] == second[1, 3]
    assert observability.read_device_counters() == view

    step(_batch(2), _batch(2))
    third = _last(step, name)
    view = step.device_counters()
    assert view[f"{name}.steps"] == 3
    assert view[f"{name}.total.0.0"] == (first + second + third)[0, 0]
    assert view[f"{name}.last.0.0"] == third[0, 0]


def test_counters_reach_the_registry_and_prometheus_after_a_read_only():
    step = _step()
    step(_batch(), _batch())
    key = f"{scopes.MOE_FULL_BUFFER_CHUNKS}.total.0"
    assert key not in monitor.all_stats()
    assert "moe_full_buffer_chunks" not in observability.prometheus_text()
    step.device_counters()
    assert key in monitor.all_stats()
    assert "moe_full_buffer_chunks" in observability.prometheus_text()


class Plain(nn.Layer):
    def __init__(self):
        super().__init__()
        self.emb = nn.Embedding(V, H)
        self.head = nn.Linear(H, V)

    def forward(self, ids):
        return self.head(self.emb(ids))


def test_a_step_without_an_emitter_is_the_plain_step():
    """No ``counters`` in the carry, the outputs of the step as they
    were, and nothing for a reader to read."""
    step = _step(Plain())
    step(_batch(), _batch())
    assert step.counter_carry() is None
    assert sorted(step._scaler_state) == sorted(step._aux_keys())
    compiled = step._compiled[True].lower(
        *_step_args(step, _batch())).compile()
    n_out = len(jax.tree.leaves(compiled.out_info))
    assert n_out == (1 + len(step._params)
                     + len(jax.tree.leaves(step._opt_state))
                     + len(step._aux_keys()))
    assert step.device_counters() == {}


def test_eager_emission_is_a_no_op():
    assert not observability.collecting()
    assert observability.device_counter("test.eager", jnp.ones(4)) is None
    net = Net()
    net(paddle.to_tensor(np.asarray(_batch())))       # eager expert layers
    assert observability.read_device_counters() == {}
    assert not any(k.startswith("test.eager") for k in monitor.all_stats())


def test_eval_step_collects_nothing():
    step = _step()
    loss, _ = step.eval_step(_batch(), _batch())
    assert np.isfinite(float(loss))
    assert step.counter_carry() is None


class InLoop(nn.Layer):
    def __init__(self):
        super().__init__()
        self.lin = nn.Linear(H, V)
        self.emb = nn.Embedding(V, H)

    def forward(self, ids):
        def body(row):
            observability.device_counter("test.in_loop", jnp.sum(row))
            return row * 2
        return self.lin(paddle.Tensor(jax.lax.map(body, self.emb(ids).data)))


def test_an_emission_inside_a_loop_body_raises_with_the_name():
    step = _step(InLoop())
    with pytest.raises(ValueError, match="test.in_loop.*lax.map"):
        step(_batch(), _batch())


@pytest.mark.parametrize("value, error, said", [
    (np.zeros(257, np.int32), ValueError, "test.refused.*1028 bytes"),
    (np.zeros(4, np.float16), TypeError, "test.refused.*float16"),
], ids=["over_1KiB", "not_int32_or_float32"])
def test_a_value_a_counter_cannot_hold_is_refused_by_name(value, error, said):
    with device_counters.collect():
        with pytest.raises(error, match=said):
            observability.device_counter("test.refused", value)
        observability.device_counter("test.refused", np.zeros(256, np.int32))
