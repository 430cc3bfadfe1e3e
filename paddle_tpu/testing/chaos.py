"""Chaos smoke flows: training and serving under injected faults.

Trains a tiny model twice — once fault-free, once under a canned chaos
spec (checkpoint-fs write flakes, one DataLoader worker hard-killed
mid-epoch, SIGTERM mid-training) — and reports failure unless the
faulted run *resumes to completion with bitwise-identical final
parameters*.  This is the executable proof that the recovery paths
(utils/fs retry loop, digest-verified checkpoint fallback/publish,
DataLoader worker respawn, TrainEpochRange preemption save) actually
compose into "preemptible pods can train" (ROADMAP north star;
reference: fluid/incubate/checkpoint + framework/io/fs.cc +
fluid/reader.py SIGCHLD handling).

Lives inside the package (not tools/) so forkserver DataLoader workers
can unpickle :class:`SmokeDataset` regardless of how the driver was
launched; ``tools/chaos_smoke.py`` is the CLI entry point and
``tests/test_fault_tolerance.py`` runs :func:`main` in-process.

:func:`serving_main` is the serving-engine counterpart (ISSUE 4): under
injected dispatcher faults, queue-full shedding, and in-queue deadline
expiry, every *accepted* request must still get a bitwise-correct
response or a clean shed/deadline error — never a hang or a wrong
answer.  Bitwise is provable here because :func:`make_dyadic_model`
keeps every weight and input a small dyadic rational, so float
accumulation is exact in any batching/padding order.
"""
from __future__ import annotations

import os
import shutil
import signal
import sys
import tempfile

import numpy as np

# The canned chaos: two transient flakes on checkpoint writes (absorbed
# by the fs retry loop), and a DataLoader worker hard-killed when it
# picks up batch 1 (absorbed by respawn + re-enqueue; matching on the
# batch, not a worker id, is start-order independent).  SIGTERM is
# raised separately mid-epoch by _train below.
CHAOS_SPEC = ("fs.open_write:count=2,exc=TransientFSError;"
              "mp.worker_batch:count=1,action=exit,code=43,match=batch=1")

N, D, BATCH = 32, 4, 8


class SmokeDataset:
    """Deterministic regression data; module-level so forkserver
    DataLoader workers can unpickle it."""

    def __init__(self):
        rng = np.random.RandomState(7)
        self.x = rng.randn(N, D).astype(np.float32)
        self.y = (self.x @ rng.randn(D, 1).astype(np.float32))

    def __len__(self):
        return N

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _build():
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    paddle.seed(1234)
    net = nn.Linear(D, 1)
    opt = optimizer.SGD(learning_rate=0.05, parameters=net.parameters())
    return net, opt


def _train(ckpt_dir, epochs, num_workers=2, sigterm_after_epoch=None,
           verbose=False):
    """One training process: build fresh objects, auto-resume, run.
    Returns final weights, or None when SIGTERM ended the run early."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.io import DataLoader
    from paddle_tpu.utils.checkpoint import TrainEpochRange

    net, opt = _build()
    loader = DataLoader(SmokeDataset(), batch_size=BATCH, shuffle=False,
                        num_workers=num_workers)
    r = TrainEpochRange(epochs, ckpt_dir, model=net, opt=opt)
    try:
        for epoch in r:
            for xb, yb in loader:
                loss = F.mse_loss(net(xb), yb)
                loss.backward()
                opt.step()
                opt.clear_grad()
            if verbose:
                print(f"  epoch {epoch}: loss={float(loss):.6f}")
            if sigterm_after_epoch is not None \
                    and epoch == sigterm_after_epoch:
                # the preemption notice arrives mid-training; the range
                # saves at this epoch boundary and exits cleanly
                os.kill(os.getpid(), signal.SIGTERM)
    except SystemExit as e:
        if e.code not in (0, None):
            raise
        assert r.preempted, "SystemExit without a preemption request"
        return None
    finally:
        pool = getattr(loader, "_mp_pool", None)
        if pool is not None:
            pool.close()
            loader._mp_pool = None
    return net.weight.numpy().copy(), net.bias.numpy().copy()


def main(epochs=4, verbose=False, workdir=None):
    import json

    import paddle_tpu as paddle
    from paddle_tpu import observability
    from paddle_tpu.testing import fault
    from paddle_tpu.utils import fs, monitor

    own_tmp = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_smoke_")
    scheme = "chaossmoke"
    # checkpoint store: local dir mounted under a registered scheme with
    # the retry wrapper — the 'remote store with transient failures'
    # stand-in the fs flake targets
    fs.register_fs(scheme, fs.PrefixStripFS(fs.LocalFS(), scheme),
                   retry=True)
    old_backoff = paddle.get_flags("fs_retry_backoff_s")
    paddle.set_flags({"fs_retry_backoff_s": 0.01})
    try:
        if verbose:
            print("== reference run (fault-free) ==")
        ref = _train(f"{workdir}/ref_ckpt", epochs, verbose=verbose)
        assert ref is not None

        if verbose:
            print("== chaos run ==")
        chaos_dir = f"{scheme}://{workdir}/chaos_ckpt"
        monitor.stat_reset()
        # black box: faulted runs must leave a readable flight record —
        # the SIGTERM preemption notice triggers the dump (the recorder
        # installs its handler first; the epoch range's chains to it)
        flight_path = os.path.join(workdir, "flight_record.json")
        observability.enable(capacity=4096)
        observability.install_flight_recorder(path=flight_path)
        fault.arm(CHAOS_SPEC, seed=0)
        try:
            out = _train(chaos_dir, epochs, verbose=verbose,
                         sigterm_after_epoch=1)
        finally:
            fault.disarm()
        if out is not None:
            print("FAIL: SIGTERM did not stop the first chaos run",
                  file=sys.stderr)
            return 1

        if verbose:
            print("== resume after preemption ==")
        out = _train(chaos_dir, epochs, verbose=verbose)
        if out is None:
            print("FAIL: resume run ended early", file=sys.stderr)
            return 1

        # the black box must exist and show what actually happened
        flight_problems = []
        if not os.path.exists(flight_path):
            flight_problems.append(
                "faulted run left no flight-recorder dump")
        else:
            with open(flight_path) as f:
                box = json.load(f)
            if box.get("reason") != "SIGTERM":
                flight_problems.append(
                    f"flight dump reason {box.get('reason')!r}, "
                    f"expected 'SIGTERM'")
            kinds = {e.get("kind") for e in box.get("events", [])}
            if "fault" not in kinds:
                flight_problems.append(
                    "flight dump lacks the injected fault event")
            if "checkpoint" not in kinds:
                flight_problems.append(
                    "flight dump lacks checkpoint events")

        stats = monitor.all_stats()
        if verbose:
            print("recovery stats:", {k: v for k, v in sorted(
                stats.items()) if not k.startswith("fault.")})
        problems = list(flight_problems)
        if stats.get("fs.retries", 0) < 2:
            problems.append(f"fs flake not retried "
                            f"(fs.retries={stats.get('fs.retries', 0)})")
        if stats.get("dataloader.worker_restarts", 0) < 1:
            problems.append("killed worker was not respawned")
        if stats.get("checkpoint.preempt_saves", 0) < 1:
            problems.append("SIGTERM did not trigger a boundary save")
        if not np.array_equal(out[0], ref[0]) \
                or not np.array_equal(out[1], ref[1]):
            problems.append(
                f"final params differ from fault-free run "
                f"(max |dW|={np.abs(out[0] - ref[0]).max():.3e})")
        if problems:
            for p in problems:
                print(f"FAIL: {p}", file=sys.stderr)
            return 1
        print("chaos_smoke OK: training survived fs flakes, a worker "
              "kill, and SIGTERM preemption with bitwise-identical "
              "final params (+ a readable flight-recorder black box)")
        return 0
    finally:
        observability.uninstall_flight_recorder()
        observability.disable()
        paddle.set_flags(old_backoff)
        fs._REGISTRY.pop(scheme, None)
        if own_tmp:
            shutil.rmtree(workdir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Serving chaos (ISSUE 4): dispatcher flakes + shedding + deadlines
# ---------------------------------------------------------------------------

# Dispatcher flakes: 3 random fires across the run, seeded for replay.
# The engine retries a flaked batch (inference is pure), and with
# dispatch_retries=3 a rule capped at count=3 can NEVER exhaust a
# batch's 4 attempts — so every accepted request must come back correct.
SERVING_CHAOS_SPEC = "serving.dispatch:p=0.3,count=3"


def make_dyadic_model(in_dim=8, hidden=16, out_dim=4):
    """A tiny MLP whose weights are small dyadic rationals (k/8).

    With inputs that are also dyadic (k/4), every product and partial
    sum is exactly representable in float32, so outputs are bitwise
    identical regardless of batch coalescing, padding, or reduction
    order — the property the serving chaos/smoke gates assert."""
    import numpy as np

    from paddle_tpu import nn

    model = nn.Sequential(nn.Linear(in_dim, hidden), nn.ReLU(),
                          nn.Linear(hidden, out_dim))
    for p in model.parameters():
        p.set_value(np.round(p.numpy() * 8.0) / 8.0)
    return model


def serving_main(requests=40, clients=4, verbose=False):
    """Serving chaos gate; returns 0 on success, 1 on failure."""
    import tempfile
    import threading
    import time

    import paddle_tpu as paddle
    from paddle_tpu import inference, jit, serving
    from paddle_tpu.jit import InputSpec
    from paddle_tpu.testing import fault
    from paddle_tpu.utils import monitor

    paddle.seed(5)
    model = make_dyadic_model()
    prefix = os.path.join(tempfile.mkdtemp(prefix="serve_chaos_"), "m")
    jit.save(model, prefix, input_spec=[InputSpec([None, 8], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))

    rng = np.random.RandomState(17)
    reqs = [(rng.randint(-8, 9, (rng.randint(1, 5), 8)) / 4.0)
            .astype(np.float32) for _ in range(requests)]
    refs = [np.asarray(pred.run([x])[0]) for x in reqs]

    max_queue = 8
    engine = serving.InferenceEngine(pred, max_batch_size=8,
                                     batch_timeout_ms=5.0,
                                     max_queue=max_queue,
                                     dispatch_retries=3)
    engine.warmup()

    problems = []
    monitor.stat_reset()
    fault.arm(SERVING_CHAOS_SPEC, seed=1)
    try:
        # -- concurrent traffic under dispatcher flakes ------------------
        outcomes = [None] * requests

        def client(idx):
            for i in range(idx, requests, clients):
                try:
                    outcomes[i] = engine.infer_sync(
                        [reqs[i]], timeout=30)
                except Exception as e:  # noqa: BLE001 - gated below
                    outcomes[i] = e

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        for i, (out, ref) in enumerate(zip(outcomes, refs)):
            if isinstance(out, Exception):
                problems.append(
                    f"accepted request {i} failed under chaos: "
                    f"{type(out).__name__}: {out}")
            elif out is None:
                problems.append(f"request {i} hung (no outcome)")
            elif not np.array_equal(out[0], ref):
                problems.append(
                    f"request {i}: WRONG ANSWER under chaos (max "
                    f"|d|={np.abs(out[0] - ref).max():.3e})")

        # -- deterministic queue-full shedding ---------------------------
        engine.pause()
        burst = []
        for i in range(max_queue + 4):
            try:
                burst.append(engine.infer([reqs[i % requests]]))
            except serving.QueueFull:
                burst.append("shed")
        n_shed = sum(1 for b in burst if b == "shed")
        if n_shed != 4:
            problems.append(f"expected exactly 4 sheds from a "
                            f"{max_queue + 4}-burst into a paused "
                            f"{max_queue}-queue, got {n_shed}")

        engine.resume()
        accepted = [b for b in burst if b != "shed"]
        for i, f in enumerate(accepted):
            try:
                f.result(timeout=30)
            except Exception as e:  # noqa: BLE001
                problems.append(f"post-pause request {i} failed: "
                                f"{type(e).__name__}: {e}")

        # -- in-queue deadline expiry (never occupies a batch slot) ------
        engine.pause()          # idle queue now: the probe is admitted
        doomed = engine.infer([reqs[0]], deadline_ms=1.0)
        time.sleep(0.02)
        engine.resume()
        try:
            doomed.result(timeout=30)
            problems.append("1 ms deadline request was served instead "
                            "of expiring in-queue")
        except serving.DeadlineExceeded:
            pass
        except Exception as e:  # noqa: BLE001
            problems.append(f"deadline request died oddly: "
                            f"{type(e).__name__}: {e}")
    finally:
        fault.disarm()
    engine.drain(timeout=30)
    stats = engine.stats()
    engine.close()

    fired = monitor.get_stat("fault.fired.serving.dispatch")
    if fired < 1:
        problems.append("chaos spec never fired a dispatcher fault "
                        "(nothing was actually tested)")
    if stats["counters"]["dispatch_retries"] < fired:
        problems.append(
            f"dispatcher fired {fired} faults but only "
            f"{stats['counters']['dispatch_retries']} retries ran")
    if stats["recompiles_after_warmup"] != 0:
        problems.append(f"hot path recompiled "
                        f"{stats['recompiles_after_warmup']}x under chaos")
    if verbose:
        print(f"serving chaos stats: faults={fired} "
              f"retries={stats['counters']['dispatch_retries']} "
              f"shed={stats['counters']['shed']} "
              f"expired={stats['counters']['deadline_expired']} "
              f"batches={stats['counters']['batches']}")
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    print("serving chaos OK: dispatcher flakes retried, queue-full "
          "shed cleanly, deadlines expired in-queue, every served "
          "response bitwise-correct")
    return 0


# ---------------------------------------------------------------------------
# Generation chaos (ISSUE 7): decode flakes + mid-generation deadlines
# ---------------------------------------------------------------------------

# Decode-step flakes: the scheduler retries a flaked step (the step is
# functional over the KV pool, and injected faults fire before
# dispatch), and with decode_retries=3 a rule capped at count=3 can
# never exhaust a step's 4 attempts — every admitted sequence must
# stream to a clean finish.
GENERATION_CHAOS_SPEC = "serving.decode_step:p=0.3,count=3"


def make_dyadic_lm(**kw):
    """A tiny PagedDecoderLM with k/64 dyadic weights (see
    make_dyadic_model): per-row decode math reproduces bitwise in any
    slot/batch/page placement, which is what makes the admission-order
    parity gate below exact instead of tolerance-based."""
    from paddle_tpu.serving import PagedDecoderLM

    kw.setdefault("vocab_size", 32)
    kw.setdefault("hidden", 16)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("seed", 3)
    return PagedDecoderLM(dyadic=True, **kw)


def generation_main(requests=18, clients=3, verbose=False):
    """Generative serving chaos gate; returns 0 on success, 1 on failure.

    Asserts, under injected decode flakes:
      * every admitted sequence streams to a clean finish with tokens
        BITWISE-identical to a fault-free serial run in a different
        admission order (continuous batching must not change results);
      * a mid-generation deadline expiry evicts its sequence with
        DeadlineExceeded after streaming some tokens;
      * page-pool accounting returns to zero (no leaked pages) and the
        decode hot path never recompiles.
    """
    import threading
    import time

    from paddle_tpu import serving
    from paddle_tpu.testing import fault
    from paddle_tpu.utils import monitor

    model = make_dyadic_lm()
    mk_engine = lambda: serving.GenerationEngine(  # noqa: E731
        model, num_slots=4, page_size=4, max_context=64,
        max_queue=4 * requests, decode_retries=3)

    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 32, rng.randint(1, 9)).tolist()
               for _ in range(requests)]
    budgets = [int(rng.randint(3, 9)) for _ in range(requests)]

    problems = []
    monitor.stat_reset()
    engine = mk_engine()
    engine.warmup()
    fault.arm(GENERATION_CHAOS_SPEC, seed=1)
    try:
        # -- concurrent ragged traffic under decode flakes ---------------
        outcomes = [None] * requests

        def client(idx):
            for i in range(idx, requests, clients):
                try:
                    got = []
                    stream = engine.generate(prompts[i],
                                             max_new_tokens=budgets[i],
                                             temperature=0.7, seed=i)
                    for tok in stream.tokens(timeout=60):
                        got.append(tok)      # exercise streaming
                    if got != stream.result(0):
                        raise AssertionError(
                            "streamed tokens != final result")
                    outcomes[i] = got
                except Exception as e:  # noqa: BLE001 - gated below
                    outcomes[i] = e

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        for i, out in enumerate(outcomes):
            if isinstance(out, Exception):
                problems.append(
                    f"admitted sequence {i} failed under chaos: "
                    f"{type(out).__name__}: {out}")
            elif out is None or len(out) != budgets[i]:
                problems.append(
                    f"sequence {i}: {0 if out is None else len(out)} "
                    f"tokens, budget {budgets[i]}")
    finally:
        fault.disarm()

    # -- admission-order parity: serial fault-free run, reversed order --
    ref_engine = mk_engine()
    ref_engine.warmup()
    refs = [None] * requests
    for i in reversed(range(requests)):
        refs[i] = ref_engine.generate_sync(
            prompts[i], timeout=60, max_new_tokens=budgets[i],
            temperature=0.7, seed=i)
    for i, (out, ref) in enumerate(zip(outcomes, refs)):
        if isinstance(out, list) and out != ref:
            problems.append(
                f"sequence {i}: tokens differ from serial run "
                f"(admission order leaked into results): {out} != {ref}")
    ref_engine.close()

    # -- mid-generation deadline expiry (deterministic via pause; the
    # deadline is generous so even a loaded runner streams two tokens
    # before the pause lets it lapse) ------------------------------------
    doomed = engine.generate(prompts[0], max_new_tokens=40,
                             deadline_ms=2000.0)
    it = doomed.tokens(timeout=30)
    first = []
    try:
        first.append(next(it))          # decoding has demonstrably begun
        first.append(next(it))
        engine.pause()
        time.sleep(2.2)                 # deadline lapses mid-generation
        engine.resume()
        for _ in it:
            pass
        problems.append("mid-generation deadline did not expire")
    except serving.DeadlineExceeded:
        if len(first) < 2:
            problems.append("deadline expired before decoding began "
                            "(not a MID-generation expiry)")
    except Exception as e:  # noqa: BLE001
        problems.append(f"deadline sequence died oddly: "
                        f"{type(e).__name__}: {e}")
    finally:
        engine.resume()                 # never leave the engine paused

    engine.drain(timeout=60)
    stats = engine.stats()
    engine.close()

    fired = monitor.get_stat("fault.fired.serving.decode_step")
    if fired < 1:
        problems.append("chaos spec never fired a decode fault "
                        "(nothing was actually tested)")
    if stats["counters"]["decode_retries"] < fired:
        problems.append(
            f"decode fired {fired} faults but only "
            f"{stats['counters']['decode_retries']} retries ran")
    if stats["recompiles_after_warmup"] != 0:
        problems.append(f"decode hot path recompiled "
                        f"{stats['recompiles_after_warmup']}x under chaos")
    if stats["page_pool"]["in_use"] != 0:
        problems.append(f"page pool leaked "
                        f"{stats['page_pool']['in_use']} pages")
    if stats["counters"]["pages_allocated"] \
            != stats["counters"]["pages_freed"]:
        problems.append(
            f"page accounting: {stats['counters']['pages_allocated']} "
            f"allocated vs {stats['counters']['pages_freed']} freed")
    if verbose:
        print(f"generation chaos stats: faults={fired} "
              f"retries={stats['counters']['decode_retries']} "
              f"expired={stats['counters']['deadline_expired']} "
              f"steps={stats['counters']['decode_steps']} "
              f"occupancy={stats['mean_slot_occupancy']:.2f}")
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    print("generation chaos OK: decode flakes retried, tokens bitwise-"
          "identical to serial admission, mid-generation deadline "
          "evicted cleanly, page pool fully reclaimed")
    return 0


# ---------------------------------------------------------------------------
# Reshard chaos (ISSUE 8): kill mid-run, restore onto a DIFFERENT mesh
# ---------------------------------------------------------------------------

def _reshard_feed():
    """The deterministic regression feed every mesh-drill incarnation
    (reference runs, chaos runs, supervised children — whatever the
    process) must reconstruct identically, or the loss-parity gates
    compare divergent trajectories."""
    import numpy as np

    rng = np.random.RandomState(7)
    xs = rng.standard_normal((64, D)).astype(np.float32)
    ys = xs @ rng.standard_normal((D, 1)).astype(np.float32)
    return {"x": xs, "y": ys}


def _reshard_build(lr=0.05):
    """One fleet-sharded static training program (the 'unchanged user
    code' both mesh sizes run)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import distributed as dist, optimizer

    paddle.seed(1234)
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [None, D], "float32")
        y = paddle.static.data("y", [None, 1], "float32")
        pred = paddle.static.nn.fc(x, 8)
        pred = paddle.static.nn.fc(F.relu(pred), 1)
        loss = F.mse_loss(pred, y)
        f = dist.fleet
        f.init(is_collective=True, strategy=dist.DistributedStrategy())
        opt = f.distributed_optimizer(optimizer.Adam(learning_rate=lr))
        opt.minimize(loss)
    return main, loss, paddle.static.Executor()


def reshard_main(steps=12, save_every=4, kill_after=6, verbose=False,
                 workdir=None):
    """Mid-run mesh-size change via sharded checkpoint restore.

    Reference run: the training program on mesh ``{dp: 8}``,
    uninterrupted, recording the per-step loss trajectory.  Chaos run:
    same program, sharded SnapshotStore saves every ``save_every``
    steps, a fault injected at ``executor.run`` kills step
    ``kill_after`` — then the program is REBUILT on mesh ``{dp: 2}``,
    restored from the (digest-verified, per-shard) snapshot, resharded
    onto the smaller mesh, and trained to completion.  Gates:

    - the restore itself is bitwise (gathered params == params at the
      save point on the old mesh);
    - the post-restore loss trajectory matches the uninterrupted run's
      same steps (rtol 1e-5 — reduction order differs across dp
      degrees);
    - the injected kill actually fired (the run was really interrupted).
    """
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.testing import fault
    from paddle_tpu.utils.checkpoint import SnapshotStore

    import jax
    if len(jax.devices()) < 8:
        print("FAIL: reshard scenario needs 8 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)
        return 1

    own_tmp = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_reshard_")
    feed = _reshard_feed()

    was_static = paddle.in_static_mode() \
        if hasattr(paddle, "in_static_mode") else False
    paddle.enable_static()
    try:
        # -- reference: uninterrupted on mesh {dp: 8} ----------------------
        init_mesh({"dp": 8})
        main, loss, exe = _reshard_build()
        init_mesh({"dp": 8})
        ref_losses = [float(exe.run(main, feed=feed,
                                    fetch_list=[loss])[0])
                      for _ in range(steps)]
        exe.close()
        paddle.static.reset_default_programs()
        if verbose:
            print(f"reference (mesh dp=8): {ref_losses}")

        # -- chaos: save every N, injected kill, reshard to {dp: 2} --------
        store = SnapshotStore(f"{workdir}/ckpt")
        init_mesh({"dp": 8})
        main, loss, exe = _reshard_build()
        init_mesh({"dp": 8})
        saved_at = -1
        saved_params = None
        killed = False
        fault.arm(f"executor.run:count=1,after={kill_after}")
        try:
            for step in range(steps):
                try:
                    exe.run(main, feed=feed, fetch_list=[loss])
                except fault.FaultInjected:
                    killed = True
                    break
                if (step + 1) % save_every == 0:
                    store.save(step, {"train": exe.sharded_state(main)})
                    saved_at = step
                    saved_params = {
                        k: np.asarray(v).copy() for k, v in
                        exe.sharded_state(main)._getter()
                        ["params"].items()}
        finally:
            fault.disarm()
        exe.close()
        paddle.static.reset_default_programs()
        if not killed:
            print("FAIL: injected executor.run fault never fired",
                  file=sys.stderr)
            return 1
        if saved_at < 0:
            print("FAIL: kill arrived before the first snapshot "
                  "(raise kill_after or lower save_every)",
                  file=sys.stderr)
            return 1
        if verbose:
            print(f"killed at step {kill_after}, last snapshot at "
                  f"step {saved_at}")

        init_mesh({"dp": 2})  # the replacement pod is a different size
        main2, loss2, exe2 = _reshard_build()
        init_mesh({"dp": 2})
        ss = exe2.sharded_state(main2)
        store.restore({"train": ss})
        restored = {k: np.asarray(v) for k, v in
                    ss._getter()["params"].items()}
        problems = []
        for k in saved_params:
            if not np.array_equal(restored[k], saved_params[k]):
                problems.append(
                    f"restored param {k} not bitwise-identical across "
                    f"the mesh-8 -> mesh-2 reshard")
        cont = [float(exe2.run(main2, feed=feed,
                               fetch_list=[loss2])[0])
                for _ in range(steps - saved_at - 1)]
        exe2.close()
        paddle.static.reset_default_programs()
        if verbose:
            print(f"resumed (mesh dp=2):  {cont}")

        expect = ref_losses[saved_at + 1:]
        try:
            np.testing.assert_allclose(cont, expect, rtol=1e-5)
        except AssertionError as e:
            problems.append(
                f"post-restore loss trajectory diverged from the "
                f"uninterrupted run: {e}")
        if problems:
            for p in problems:
                print(f"FAIL: {p}", file=sys.stderr)
            return 1
        print("chaos reshard OK: killed mid-run on mesh dp=8, restored "
              f"the step-{saved_at} sharded snapshot onto mesh dp=2 "
              "(bitwise params), loss trajectory matches the "
              "uninterrupted run")
        return 0
    finally:
        if not was_static:
            paddle.disable_static()
        import paddle_tpu.static as _st
        _st.reset_default_programs()
        if own_tmp:
            shutil.rmtree(workdir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Hot-swap chaos (ISSUE 18): digest-verified weight swaps under concurrent
# traffic, one corrupted snapshot, one supervisor-restarted replica crash
# ---------------------------------------------------------------------------

def _scaled_artifact(scale, workdir, tag):
    """``jit.save`` the dyadic inference model with every weight scaled
    by ``scale``.  Power-of-two scales keep every value exactly
    representable, so each published version has its own bitwise-exact
    reference outputs — which is what lets the swap gate attribute
    every served response to exactly one weights version."""
    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.jit import InputSpec

    paddle.seed(5)
    model = make_dyadic_model()
    for p in model.parameters():
        p.set_value(p.numpy() * scale)
    prefix = os.path.join(workdir, f"m_{tag}")
    jit.save(model, prefix, input_spec=[InputSpec([None, 8], "float32")])
    return prefix


def _swap_serving_entry(prefix, port, state_file, stop_file):
    """Supervised serving replica (module-level so spawn can pickle it).

    Binds the HTTP plane not-ready, warms the batch buckets, marks
    ready, then serves until ``stop_file`` appears.  The FIRST
    incarnation hard-crashes (``os._exit``) about a second after going
    ready — with the parent's clients mid-request — so the supervisor
    must restart it and the replacement must re-warm and go ready
    again before traffic recovers."""
    import threading
    import time

    from paddle_tpu import inference, serving

    pred = inference.create_predictor(inference.Config(prefix))
    engine = serving.InferenceEngine(pred, max_batch_size=8,
                                     batch_timeout_ms=5.0)
    srv = serving.ServingServer(engine, port=port, ready=False).start()
    engine.warmup()
    srv.mark_ready()
    if not os.path.exists(state_file):
        with open(state_file, "w") as f:
            f.write("1")

        def _die():
            time.sleep(1.0)
            os._exit(9)         # a hard replica crash, mid-traffic

        threading.Thread(target=_die, daemon=True).start()
    while not os.path.exists(stop_file):
        time.sleep(0.05)
    srv.close()
    engine.drain(timeout=10.0)
    engine.close()


def swap_main(requests=16, clients=3, verbose=False, workdir=None,
              supervised=True):
    """Swap-under-fire gate; returns 0 on success, 1 on failure.

    Part one (in-process, engines under concurrent traffic): a
    :class:`~paddle_tpu.serving.hotswap.WeightWatcher` applies three
    live weight swaps (versions 1..3) to an InferenceEngine AND a
    GenerationEngine while client threads hammer both, then one
    deliberately corrupted snapshot (version 4) must be rejected with
    the engines still serving version 3.  Gates: every response is
    bitwise-correct for *some* published version (inference batches
    run under exactly one predictor, so no response may mix versions;
    generation sequences that demonstrably ran inside one version must
    match that version's serial reference), each applied version is
    bitwise-verified by a settled serial pass, ``/healthz`` readiness
    stays green through every applied swap, zero hot-path recompiles,
    zero stranded futures, and the page pool is fully reclaimed.

    Part two (``supervised=True``): a :class:`ServingSupervisor`
    replica crashes hard mid-traffic; the supervisor restarts it, the
    replacement re-warms and goes ready, clients ride through via the
    reconnect path (``client.reconnects``), and post-restart responses
    are again bitwise-correct.
    """
    import threading
    import time

    from paddle_tpu import inference, serving
    from paddle_tpu.serving.hotswap import (PARAMS_PAYLOAD, WeightWatcher,
                                            publish_weights)
    from paddle_tpu.utils import monitor
    from paddle_tpu.utils.checkpoint import SnapshotStore

    own_tmp = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_swap_")
    problems = []
    monitor.stat_reset()
    scales = {0: 1.0, 1: 0.5, 2: 0.25, 3: 2.0}

    # -- per-version bitwise references -----------------------------------
    prefixes = {v: _scaled_artifact(s, workdir, f"v{v}")
                for v, s in scales.items()}
    preds = {v: inference.create_predictor(inference.Config(prefixes[v]))
             for v in scales}
    rng = np.random.RandomState(17)
    reqs = [(rng.randint(-8, 9, (rng.randint(1, 5), 8)) / 4.0)
            .astype(np.float32) for _ in range(requests)]
    inf_refs = {v: [np.asarray(preds[v].run([x])[0]) for x in reqs]
                for v in scales}
    for v in (1, 2, 3):
        if all(np.array_equal(a, b)
               for a, b in zip(inf_refs[v], inf_refs[0])):
            problems.append(f"version {v} artifact is output-identical "
                            f"to version 0 (swap would be unobservable)")

    base_params = {k: np.asarray(v).copy()
                   for k, v in make_dyadic_lm().params.items()}
    params_for = {v: {k: a * s for k, a in base_params.items()}
                  for v, s in scales.items()}
    prompts = [rng.randint(0, 32, rng.randint(1, 9)).tolist()
               for _ in range(6)]
    budgets = [int(rng.randint(3, 7)) for _ in prompts]

    # generation references: ONE warmed engine, serially hot-swapped
    # through the version sequence (idle swaps — also a deterministic
    # exercise of the staged-commit path itself)
    ref_gen = serving.GenerationEngine(make_dyadic_lm(), num_slots=4,
                                       page_size=4, max_context=64,
                                       max_queue=64)
    ref_gen.warmup()
    gen_refs = {}
    for v in sorted(scales):
        if v:
            ref_gen.swap_weights(params_for[v], v)
        gen_refs[v] = [ref_gen.generate_sync(
            prompts[i], timeout=60, max_new_tokens=budgets[i],
            temperature=0.7, seed=i) for i in range(len(prompts))]
    ref_stats = ref_gen.stats()
    ref_gen.close()
    if ref_stats["counters"]["weight_swaps"] != 3 \
            or ref_stats["recompiles_after_warmup"] != 0:
        problems.append(
            f"reference engine: {ref_stats['counters']['weight_swaps']} "
            f"swaps, {ref_stats['recompiles_after_warmup']} recompiles "
            f"(expected 3 swaps, 0 recompiles)")

    # -- part one: live engines, watcher, fire ------------------------------
    engine = serving.InferenceEngine(preds[0], max_batch_size=8,
                                     batch_timeout_ms=5.0,
                                     max_queue=8 * requests)
    engine.warmup()
    gen = serving.GenerationEngine(make_dyadic_lm(), num_slots=4,
                                   page_size=4, max_context=64,
                                   max_queue=256)
    gen.warmup()
    srv = serving.ServingServer(engine, generation=gen, port=0).start()
    store = SnapshotStore(os.path.join(workdir, "weights"))
    watcher = WeightWatcher(store, engine=engine, generation=gen,
                            poll_s=0.05).start()

    stop = threading.Event()
    ready_bad, versions_seen, probes = [], set(), [0]
    inf_outcomes, gen_outcomes = [], []

    def prober():
        c = serving.Client(srv.url)
        while not stop.is_set():
            h = c.healthz()
            probes[0] += 1
            if not h.get("ready") or h.get("status") != "running":
                ready_bad.append(dict(h))
            versions_seen.add(int(h.get("weights_version", -1)))
            time.sleep(0.01)

    def inf_client(idx):
        k = idx
        while not stop.is_set():
            i = k % len(reqs)
            k += clients
            try:
                out = engine.infer_sync([reqs[i]], timeout=30)
                inf_outcomes.append((i, np.asarray(out[0])))
            except Exception as e:  # noqa: BLE001 - gated below
                inf_outcomes.append((i, e))

    def gen_client(idx):
        k = idx
        while not stop.is_set():
            i = k % len(prompts)
            k += clients
            v_before = gen.weights_version
            try:
                toks = gen.generate_sync(
                    prompts[i], timeout=60, max_new_tokens=budgets[i],
                    temperature=0.7, seed=i)
                gen_outcomes.append((i, v_before, gen.weights_version,
                                     toks))
            except Exception as e:  # noqa: BLE001 - gated below
                gen_outcomes.append((i, v_before, -1, e))

    threads = [threading.Thread(target=prober, daemon=True)]
    threads += [threading.Thread(target=inf_client, args=(c,),
                                 daemon=True) for c in range(clients)]
    threads += [threading.Thread(target=gen_client, args=(c,),
                                 daemon=True) for c in range(clients)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.4)                 # traffic lands on version 0
        for v in (1, 2, 3):
            publish_weights(store, v, artifact_prefix=prefixes[v],
                            params=params_for[v])
            deadline = time.monotonic() + 60
            while watcher.version < v \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            if watcher.version != v:
                problems.append(
                    f"swap to version {v} not applied within 60s "
                    f"(last_error={watcher.last_error})")
                break
            # settled serial pass: the freshly applied version must
            # answer bitwise-correctly under its OWN references while
            # the fire traffic keeps coalescing around these requests
            for i in range(3):
                out = engine.infer_sync([reqs[i]], timeout=30)
                if not np.array_equal(out[0], inf_refs[v][i]):
                    problems.append(
                        f"version {v}: settled inference response {i} "
                        f"not bitwise (max |d|="
                        f"{np.abs(out[0] - inf_refs[v][i]).max():.3e})")
            toks = gen.generate_sync(prompts[0], timeout=60,
                                     max_new_tokens=budgets[0],
                                     temperature=0.7, seed=0)
            if toks != gen_refs[v][0]:
                problems.append(f"version {v}: settled generation not "
                                f"bitwise ({toks} != {gen_refs[v][0]})")
            if verbose:
                print(f"swap v{v} applied "
                      f"(engine={engine.weights_version} "
                      f"gen={gen.weights_version})")
            time.sleep(0.4)             # fire window on this version

        # -- the corrupted snapshot: rejected, never applied -------------
        # (stop the poller first so the byte flip is atomic w.r.t. the
        # watcher — a real corruption races the same way: the digest
        # check, not timing, is the defense)
        watcher.stop()
        publish_weights(store, 4, artifact_prefix=prefixes[3],
                        params=params_for[3])
        snap = store.latest_snapshot()
        path = os.path.join(store.dir, snap["dir"],
                            f"{PARAMS_PAYLOAD}.pdparams")
        with open(path, "r+b") as f:
            f.seek(20)
            b = f.read(1)
            f.seek(20)
            f.write(bytes([b[0] ^ 0xFF]))
        import warnings as _warnings
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            got = watcher.check_once()
        if got is not None or watcher.last_rejected != 4:
            problems.append(
                f"corrupted snapshot not rejected (applied={got}, "
                f"last_rejected={watcher.last_rejected})")
        if engine.weights_version != 3 or gen.weights_version != 3:
            problems.append(
                f"engines moved off version 3 after a corrupt publish "
                f"(engine={engine.weights_version}, "
                f"gen={gen.weights_version})")
        out = engine.infer_sync([reqs[0]], timeout=30)
        if not np.array_equal(out[0], inf_refs[3][0]):
            problems.append("post-corruption response no longer bitwise "
                            "at version 3")
    finally:
        stop.set()
        for t in threads:
            t.join(30)
        watcher.stop()
        srv.close()
    engine.drain(timeout=30)
    gen.drain(timeout=60)
    stats = engine.stats()
    gen_stats = gen.stats()
    engine.close()
    gen.close()

    # -- part-one gates ----------------------------------------------------
    version_set = set(scales)
    for i, res in inf_outcomes:
        if isinstance(res, Exception):
            problems.append(f"inference request {i} failed under swap "
                            f"fire: {type(res).__name__}: {res}")
        elif not any(np.array_equal(res, inf_refs[v][i])
                     for v in version_set):
            problems.append(
                f"inference request {i}: response matches NO published "
                f"version (a swap tore a batch)")
    stable = 0
    for i, v0, v1, res in gen_outcomes:
        if isinstance(res, Exception):
            problems.append(f"generation request {i} failed under swap "
                            f"fire: {type(res).__name__}: {res}")
        elif v0 == v1 and v0 in version_set:
            stable += 1
            if res != gen_refs[v0][i]:
                problems.append(
                    f"generation request {i} ran entirely under "
                    f"version {v0} but tokens differ from its serial "
                    f"reference: {res} != {gen_refs[v0][i]}")
    if stable < 1:
        problems.append("no generation request ran inside a single "
                        "weights version (fire windows too short)")
    if probes[0] < 20:
        problems.append(f"readiness poller made only {probes[0]} probes")
    if ready_bad:
        problems.append(f"readiness went red during swaps: "
                        f"{ready_bad[:3]} ({len(ready_bad)} probes)")
    if not versions_seen <= {0, 1, 2, 3}:
        problems.append(f"/healthz exposed unexpected weights versions: "
                        f"{sorted(versions_seen)}")
    if monitor.get_stat("serving.swap.applied") != 3:
        problems.append(f"serving.swap.applied="
                        f"{monitor.get_stat('serving.swap.applied')}, "
                        f"expected 3")
    if monitor.get_stat("serving.swap.rejected") != 1:
        problems.append(f"serving.swap.rejected="
                        f"{monitor.get_stat('serving.swap.rejected')}, "
                        f"expected 1")
    if stats["recompiles_after_warmup"] != 0:
        problems.append(f"inference hot path recompiled "
                        f"{stats['recompiles_after_warmup']}x across "
                        f"swaps")
    if gen_stats["recompiles_after_warmup"] != 0:
        problems.append(f"decode hot path recompiled "
                        f"{gen_stats['recompiles_after_warmup']}x "
                        f"across swaps")
    if stats["counters"].get("closed_stranded", 0):
        problems.append(f"{stats['counters']['closed_stranded']} "
                        f"futures stranded at close")
    if gen_stats["page_pool"]["in_use"] != 0 \
            or gen_stats["counters"]["pages_allocated"] \
            != gen_stats["counters"]["pages_freed"]:
        problems.append(
            f"page pool not reclaimed: in_use="
            f"{gen_stats['page_pool']['in_use']}, "
            f"{gen_stats['counters']['pages_allocated']} allocated vs "
            f"{gen_stats['counters']['pages_freed']} freed")
    if verbose:
        print(f"swap fire: {len(inf_outcomes)} inference + "
              f"{len(gen_outcomes)} generation requests "
              f"({stable} version-stable), "
              f"swaps={stats['counters']['weight_swaps']}/"
              f"{gen_stats['counters']['weight_swaps']}, probes="
              f"{probes[0]}")

    # -- part two: supervised replica crash mid-traffic --------------------
    if supervised and not problems:
        problems.extend(_swap_supervised(prefixes[0], inf_refs[0], reqs,
                                         workdir, verbose))

    if own_tmp:
        shutil.rmtree(workdir, ignore_errors=True)
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    print("chaos swap OK: three live weight swaps applied under "
          "concurrent traffic (bitwise per version, readiness green, "
          "0 recompiles), a corrupted snapshot rejected with the old "
          "weights still serving, and a crashed supervised replica "
          "restarted with clients riding through")
    return 0


def _swap_supervised(prefix, refs, reqs, workdir, verbose):
    """Part two of :func:`swap_main`: the supervised-replica crash.
    Returns a list of failure strings."""
    import socket
    import threading
    import time

    from paddle_tpu import serving
    from paddle_tpu.distributed import ServingSupervisor
    from paddle_tpu.utils import monitor

    out = []
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    url = f"http://127.0.0.1:{port}"
    state_file = os.path.join(workdir, "sv_state")
    stop_file = os.path.join(workdir, "sv_stop")

    sv = ServingSupervisor(
        _swap_serving_entry, args=(prefix, port, state_file, stop_file),
        name="swapchaos", health_url=f"{url}/healthz",
        ready_poll_s=0.1, probe_timeout_s=2.0, ready_fail_budget=50,
        hang_deadline_s=300.0, startup_timeout_s=240.0, poll_s=0.1,
        backoff_s=0.1, backoff_max_s=0.5,
        crash_window_s=600.0, crash_budget=3,
        child_env={"JAX_PLATFORMS": "cpu"}, workdir=workdir)
    box = {}

    def run_sv():
        try:
            box["result"] = sv.run()
        except Exception as e:  # noqa: BLE001 - surfaced below
            box["error"] = e

    svt = threading.Thread(target=run_sv, daemon=True)
    svt.start()

    def wait_ready(deadline_s):
        deadline = time.monotonic() + deadline_s
        c = serving.Client(url, timeout=5, reconnect_backoff_s=0.05)
        while time.monotonic() < deadline:
            try:
                if c.healthz().get("ready"):
                    return True
            except Exception:  # noqa: BLE001 - replica not up yet
                pass
            time.sleep(0.1)
        return False

    successes, failures = [], []
    b_stop = threading.Event()

    def b_client(idx):
        c = serving.Client(url, timeout=10, reconnect_backoff_s=0.1)
        k = idx
        while not b_stop.is_set():
            i = k % len(reqs)
            k += 2
            try:
                got = c.predict([reqs[i]])
                successes.append((i, np.asarray(got[0],
                                                dtype=np.float32)))
            except Exception as e:  # noqa: BLE001 - gated below
                failures.append((i, e))
            time.sleep(0.01)

    try:
        if not wait_ready(240.0):
            return ["supervised replica never became ready"]
        clients = [threading.Thread(target=b_client, args=(c,),
                                    daemon=True) for c in range(2)]
        for t in clients:
            t.start()
        # the first incarnation self-crashes ~1s after ready; wait for
        # the supervisor to notice and restart it
        deadline = time.monotonic() + 120
        while monitor.get_stat("supervisor.serving.restarts") < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        if monitor.get_stat("supervisor.serving.restarts") < 1:
            b_stop.set()
            return ["replica crash never triggered a supervised "
                    "restart"]
        if not wait_ready(240.0):
            b_stop.set()
            return ["restarted replica never became ready again"]
        # post-restart: fresh client, serial bitwise pass
        c = serving.Client(url, timeout=10)
        for i in range(3):
            got = c.predict([reqs[i]])
            arr = np.asarray(got[0], dtype=np.float32)
            if not np.array_equal(arr, refs[i]):
                out.append(f"post-restart response {i} not bitwise "
                           f"(max |d|={np.abs(arr - refs[i]).max():.3e})")
        b_stop.set()
        for t in clients:
            t.join(30)
    finally:
        b_stop.set()
        with open(stop_file, "w") as f:
            f.write("1")
        svt.join(300)
        sv.stop()

    if "error" in box:
        out.append(f"supervisor died: {type(box['error']).__name__}: "
                   f"{box['error']}")
        return out
    result = box.get("result")
    if result is None:
        out.append("supervisor did not finish after the stop file")
        return out
    if not result.clean_exit or result.attempts != 2:
        out.append(f"expected 2 incarnations ending cleanly, got "
                   f"attempts={result.attempts} "
                   f"clean_exit={result.clean_exit}")
    reasons = [r["reason"] for r in result.exit_history]
    if not reasons or "crash(exit=9)" not in reasons[0]:
        out.append(f"first exit reason {reasons[:1]} != crash(exit=9)")
    if monitor.get_stat("supervisor.serving.starts") != 2:
        out.append(f"supervisor.serving.starts="
                   f"{monitor.get_stat('supervisor.serving.starts')}, "
                   f"expected 2")
    if monitor.get_stat("supervisor.serving.ready_up") < 2:
        out.append("readiness never came up twice (no observable "
                   "not-ready -> re-warm -> ready transition)")
    if monitor.get_stat("client.reconnects") < 1:
        out.append("clients never exercised the reconnect path "
                   "(client.reconnects=0)")
    for i, arr in successes:
        if not np.array_equal(arr, refs[i]):
            out.append(f"ride-through response {i} not bitwise")
            break
    if not successes:
        out.append("no client request succeeded across the restart")
    bad = [f for _, f in failures
           if not isinstance(f, (serving.ServingError, OSError))]
    if bad:
        out.append(f"restart-window failures were not clean connection "
                   f"errors: {[type(b).__name__ for b in bad[:3]]}")
    if verbose:
        print(f"supervised: {len(successes)} ok / {len(failures)} "
              f"refused during restart, reconnects="
              f"{monitor.get_stat('client.reconnects')}, "
              f"exits={reasons}")
    return out


# ---------------------------------------------------------------------------
# Data-plane anomaly (ISSUE 15): NaN feeds, non-finite grad buckets and a
# corrupted int8 wire payload -> sentry skip -> quarantine -> rollback
# ---------------------------------------------------------------------------

# One rule per corruption class, all replayable (host rules via hit
# accounting, in-graph rules via deterministic run windows baked into
# the compiled step):
#  - a NaN batch from the loader (cleared by one skip+re-delivery);
#  - an inf gradient before reduction (in-graph, run 7);
#  - a NaN int8 block-scale on the wire (in-graph, run 9);
#  - a poisoned-feed burst right after the step-8 snapshot: batch 9
#    keeps flagging past the skip budget (quarantine), batch 10 flags
#    immediately after (rollback to the snapshot).
ANOMALY_CHAOS_SPEC = (
    "dataloader.batch:action=corrupt,mode=nan,count=1,match=batch=2;"
    "executor.grads:action=corrupt,mode=inf,count=1,after=6;"
    "grad_comm.wire:action=corrupt,mode=nan,count=1,after=8,"
    "tensor=*scales*;"
    "dataloader.batch:action=corrupt,mode=nan,count=3,match=batch=9;"
    "dataloader.batch:action=corrupt,mode=inf,count=1,match=batch=10")

AN_BATCH = 32          # rows per batch (divisible by dp=8)


class AnomalyDataset:
    """12 deterministic regression batches (module-level so any loader
    path can pickle it)."""

    def __init__(self, n_batches=12, batch=AN_BATCH, dim=8):
        rng = np.random.RandomState(13)
        self.x = rng.standard_normal(
            (n_batches * batch, dim)).astype(np.float32)
        self.y = (self.x @ rng.standard_normal((dim, 1))
                  ).astype(np.float32)

    def __len__(self):
        return self.x.shape[0]

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _anomaly_build(lr=0.05):
    """Fleet-sharded static program with int8+error-feedback grad_comm
    — the configuration whose block scales and residual carry a single
    NaN would poison without the sentry."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import distributed as dist, optimizer

    paddle.seed(1234)
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [None, 8], "float32")
        y = paddle.static.data("y", [None, 1], "float32")
        pred = paddle.static.nn.fc(x, 8)
        pred = paddle.static.nn.fc(F.relu(pred), 1)
        loss = F.mse_loss(pred, y)
        f = dist.fleet
        strat = dist.DistributedStrategy()
        strat.grad_comm = {"dtype": "int8", "error_feedback": True,
                           "scatter_threshold_KB": 0.01,
                           "block_size": 64}
        f.init(is_collective=True, strategy=strat)
        opt = f.distributed_optimizer(optimizer.Adam(learning_rate=lr))
        opt.minimize(loss)
    return main, loss, paddle.static.Executor()


def _anomaly_run(loader, exe, main, loss, steps, policy=None,
                 store=None, objects=None, save_every=4, verbose=False):
    """The training loop both the reference and chaos runs share:
    batch ``k`` drives applied step ``k``; the chaos run additionally
    reacts to the policy's ladder (retry / advance / rewind)."""
    import numpy as np

    losses = {}
    applied = cursor = 0
    while applied < steps:
        xb, yb = loader.fetch_batch(cursor)
        if policy is not None:
            policy.note_batch(cursor)
        val = float(exe.run(main, feed={"x": np.asarray(xb),
                                        "y": np.asarray(yb)},
                            fetch_list=[loss])[0])
        act = policy.poll() if policy is not None else "ok"
        if verbose:
            print(f"  step {applied} batch {cursor}: {act} "
                  f"loss={val:.6f}")
        if act == "ok":
            losses[applied] = val
            applied += 1
            cursor += 1
            if store is not None and applied % save_every == 0 \
                    and applied < steps:
                store.save(0, objects, step=applied, kind="step")
        elif act == "skip":
            continue                      # re-deliver the same batch
        elif act == "quarantine":
            cursor += 1                   # blamed: move past it
        elif act == "rollback":
            applied = cursor = policy.resume_step
    return [losses[s] for s in range(steps)]


def anomaly_main(steps=12, save_every=4, verbose=False, workdir=None):
    """Data-plane fault-tolerance gate; returns 0 on success, 1 on
    failure.  Under injected NaN feeds, a non-finite gradient bucket,
    one corrupted int8 wire payload, and a poisoned-feed burst, an
    int8+error-feedback training run must finish with its applied-step
    loss trajectory matching the fault-free run — via in-graph sentry
    skips, one batch quarantine and one snapshot rollback, with zero
    manual intervention, and every decision auditable from
    ``anomaly.*`` stats and the rollback flight dump."""
    import json

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import observability
    from paddle_tpu.distributed import AnomalyPolicy
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.io import DataLoader
    from paddle_tpu.testing import fault
    from paddle_tpu.utils import monitor
    from paddle_tpu.utils.checkpoint import SnapshotStore

    import jax
    if len(jax.devices()) < 8:
        print("FAIL: anomaly scenario needs 8 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)
        return 1

    own_tmp = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_anomaly_")
    loader = DataLoader(AnomalyDataset(), batch_size=AN_BATCH,
                        shuffle=False)
    was_static = paddle.in_static_mode() \
        if hasattr(paddle, "in_static_mode") else False
    paddle.enable_static()
    old_sentry = paddle.get_flags("anomaly_sentry")
    paddle.set_flags({"anomaly_sentry": True})
    policy = None
    try:
        # -- reference: fault-free run, same batch schedule ---------------
        init_mesh({"dp": 8})
        main, loss, exe = _anomaly_build()
        init_mesh({"dp": 8})
        ref = _anomaly_run(loader, exe, main, loss, steps)
        ref_params = {k: np.asarray(v).copy() for k, v in
                      exe.sharded_state(main)._getter()
                      ["params"].items()}
        exe.close()
        paddle.static.reset_default_programs()
        if verbose:
            print(f"reference: {ref}")

        # -- chaos run ----------------------------------------------------
        monitor.stat_reset()
        flight_path = os.path.join(workdir, "anomaly_flight.json")
        observability.enable(capacity=4096)
        observability.install_flight_recorder(path=flight_path,
                                             catch_sigterm=False)
        store = SnapshotStore(f"{workdir}/ckpt")
        # arm BEFORE the build: in-graph corrupt rules are baked into
        # the compiled step at its (single) compile
        fault.arm(ANOMALY_CHAOS_SPEC, seed=0)
        init_mesh({"dp": 8})
        main, loss, exe = _anomaly_build()
        init_mesh({"dp": 8})
        objects = {"train": exe.sharded_state(main)}
        policy = AnomalyPolicy(store=store, objects=objects,
                               skip_budget=2, rollback_budget=1,
                               sync=True).install()
        try:
            got = _anomaly_run(loader, exe, main, loss, steps,
                               policy=policy, store=store,
                               objects=objects, save_every=save_every,
                               verbose=verbose)
        finally:
            fault.disarm()
        sentry = exe.sentry_stats(main)
        compiles = exe.compile_count
        got_params = {k: np.asarray(v).copy() for k, v in
                      exe.sharded_state(main)._getter()
                      ["params"].items()}
        exe.close()
        paddle.static.reset_default_programs()
        if verbose:
            print(f"chaos:     {got}")
            print(f"policy:    {policy.result()}")
            print(f"sentry:    {sentry}")

        # -- gates --------------------------------------------------------
        problems = []
        stats = monitor.all_stats()
        res = policy.result()
        try:
            np.testing.assert_allclose(got, ref, rtol=1e-5)
        except AssertionError as e:
            problems.append(f"applied-step loss trajectory diverged "
                            f"from the fault-free run: {e}")
        if compiles != 1:
            problems.append(f"sentry/chaos run compiled {compiles}x "
                            f"(want 1 — no recompiles after warmup)")
        # the ladder must have exercised every rung exactly as staged
        if res["skips"] != 5:
            problems.append(f"anomaly skips={res['skips']}, expected 5 "
                            f"(NaN feed, inf grads, wire NaN, 2 burst "
                            f"skips)")
        if res["quarantines"] != 1 or not res["ledger"] \
                or res["ledger"][0]["batch"] != 9:
            problems.append(f"quarantine ledger wrong: "
                            f"{res['ledger']} (expected batch 9 "
                            f"blamed once)")
        if res["rollbacks"] != 1 or res["resume_step"] != 8:
            problems.append(f"expected 1 rollback to step 8, got "
                            f"{res['rollbacks']} to "
                            f"{res['resume_step']}")
        # ...and be visible in monitor stats
        for stat, want in (("anomaly.skips", 5),
                           ("anomaly.quarantines", 1),
                           ("anomaly.rollbacks", 1)):
            if stats.get(stat, 0) != want:
                problems.append(f"{stat}={stats.get(stat, 0)}, "
                                f"expected {want}")
        if not stats.get("grad_comm.nonfinite_blocks", 0):
            problems.append("grad_comm.nonfinite_blocks never counted "
                            "(quantize-time guard untested)")
        # every injected corruption actually fired (in-graph points
        # count one fire per matched tensor site, so >= 1)
        if stats.get("fault.fired.dataloader.batch", 0) != 5:
            problems.append(
                f"fault.fired.dataloader.batch="
                f"{stats.get('fault.fired.dataloader.batch', 0)}, "
                f"expected 5")
        for point in ("fault.fired.executor.grads",
                      "fault.fired.grad_comm.wire"):
            if stats.get(point, 0) < 1:
                problems.append(f"{point} never fired")
        # device-side skipped counter = every flagged step (5 skips +
        # the quarantine fire + the rollback fire); it rides the aux
        # carry as a diagnostic and the restore deliberately keeps it
        # (like the EF residuals, it is an accumulator, not state)
        if sentry is None or sentry["skipped_steps"] != 7:
            problems.append(f"sentry skipped_steps="
                            f"{None if sentry is None else sentry['skipped_steps']}"
                            f", expected 7 (one per flagged step)")
        # final weights match the fault-free run
        for k in ref_params:
            if not np.allclose(got_params[k], ref_params[k],
                               rtol=1e-5, atol=0):
                problems.append(
                    f"final param {k} diverged from the fault-free "
                    f"run (max |d|="
                    f"{np.abs(got_params[k] - ref_params[k]).max():.3e})")
        # the rollback must have left an annotated flight dump
        if not os.path.exists(flight_path):
            problems.append("rollback left no flight dump")
        else:
            with open(flight_path) as f:
                box = json.load(f)
            if box.get("reason") != "anomaly.rollback":
                problems.append(f"flight dump reason "
                                f"{box.get('reason')!r} != "
                                f"'anomaly.rollback'")
            extra = box.get("extra") or {}
            led = extra.get("ledger") or []
            if not led or led[0].get("batch") != 9:
                problems.append(f"flight dump ledger {led} does not "
                                f"blame batch 9")
            if extra.get("anomaly", {}).get("resume_step") != 8:
                problems.append("flight dump lacks the rollback's "
                                "resume_step annotation")
        if problems:
            for p in problems:
                print(f"FAIL: {p}", file=sys.stderr)
            return 1
        print("chaos anomaly OK: NaN feed, inf grad bucket and a "
              "corrupted int8 wire payload were sentry-skipped "
              "(bitwise no-ops), the poisoned-feed burst was "
              "quarantined then rolled back to the step-8 snapshot, "
              "and the applied-step loss trajectory matches the "
              "fault-free run with zero manual intervention")
        return 0
    finally:
        if policy is not None:
            policy.uninstall()
        paddle.set_flags(old_sentry)
        from paddle_tpu import observability as _obs
        _obs.uninstall_flight_recorder()
        _obs.disable()
        if not was_static:
            paddle.disable_static()
        import paddle_tpu.static as _st
        _st.reset_default_programs()
        if own_tmp:
            shutil.rmtree(workdir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Supervised self-healing (ISSUE 13): hang -> watchdog kill -> resume;
# crash -> restart onto a SMALLER mesh via reshard restore
# ---------------------------------------------------------------------------

def _supervised_entry(workdir, steps, save_every):
    """The training entrypoint the supervisor keeps alive.  Stateless
    by design: every incarnation re-detects the visible device count,
    builds the (unchanged) fleet-sharded program on mesh ``{dp: ndev}``,
    auto-resumes from the newest intact snapshot through the
    ShardedState reshard path, and trains with step-cadence snapshots.
    Faults arrive via ``FLAGS_fault_spec`` in the spawn environment."""
    import json

    import numpy as np

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.utils.checkpoint import TrainEpochRange

    ndev = len(jax.devices())            # re-detect the visible mesh
    paddle.enable_static()
    init_mesh({"dp": ndev})
    main, loss, exe = _reshard_build()
    init_mesh({"dp": ndev})
    feed = _reshard_feed()
    r = TrainEpochRange(1, f"{workdir}/ckpt", save_every_steps=save_every,
                        train=exe.sharded_state(main))
    # the step log is the parent-visible record: resume markers prove
    # which snapshot (and device count) each incarnation started from,
    # step lines carry the losses the parity gate checks
    with open(f"{workdir}/steps.jsonl", "a", buffering=1) as log:
        for _epoch in r:
            log.write(json.dumps({"event": "resume",
                                  "step": r.resume_step,
                                  "devices": ndev}) + "\n")
            for step in range(r.resume_step, steps):
                val = float(exe.run(main, feed=feed,
                                    fetch_list=[loss])[0])
                log.write(json.dumps({"step": step, "loss": val,
                                      "devices": ndev}) + "\n")
                r.step()
    exe.close()


def _sv_flaky_entry(state_file, failures=2, code=5):
    """Supervisor test fixture (module-level so spawn children can
    unpickle it): exit ``code`` for the first ``failures`` incarnations
    — the counter persists in ``state_file`` — then exit cleanly."""
    n = 0
    if os.path.exists(state_file):
        n = int(open(state_file).read())
    with open(state_file, "w") as f:
        f.write(str(n + 1))
    if n < failures:
        sys.exit(code)


def _sv_slow_start_entry(state_file):
    """Supervisor test fixture: the first incarnation beats at step
    scale then crashes; the second stays beat-silent for a while (a
    restart's recompile wall) before finishing.  The watchdog must
    judge that quiet start against ``startup_timeout_s``, not the
    step-scale deadline its retained interval window would give."""
    import time

    from paddle_tpu.distributed.supervisor import current_heartbeat

    hb = current_heartbeat()
    if not os.path.exists(state_file):
        with open(state_file, "w") as f:
            f.write("1")
        for i in range(10):
            hb.beat(i)
            time.sleep(0.02)
        # a crash, not a shutdown: on a loaded host the interpreter's
        # teardown outlasts the step-scale deadline and reads as a hang
        os._exit(3)
    time.sleep(2.0)                  # 'compiling': no step beats
    hb.beat(0)


def _sv_hang_entry(state_file, beats=6, interval=0.05):
    """Supervisor test fixture: beat the heartbeat by hand for a while,
    then wedge (sleep 600s) on the FIRST incarnation; exit cleanly on
    the second — a hang the watchdog must clear exactly once."""
    import time

    from paddle_tpu.distributed.supervisor import current_heartbeat

    if os.path.exists(state_file):
        return
    with open(state_file, "w") as f:
        f.write("1")
    hb = current_heartbeat()
    for i in range(beats):
        hb.beat(i)
        time.sleep(interval)
    time.sleep(600)


def supervise_main(steps=14, save_every=2, hang_after=5, crash_after=4,
                   verbose=False, workdir=None):
    """Self-healing training gate; returns 0 on success, 1 on failure.

    One supervised job survives, with zero manual intervention:

    1. an injected mid-step hang (``executor.step_hang`` sleep fault)
       — the watchdog misses heartbeats, escalates SIGTERM→SIGKILL,
       and restarts; the job resumes from the latest step-cadence
       snapshot;
    2. an injected hard crash (``executor.run`` exit fault) — the
       restarted incarnation sees only 4 of the original 8 devices and
       resumes via the SnapshotStore/ShardedState reshard path
       (mesh 8 → 4 is a restart, not an outage);

    and the assembled per-step loss trajectory matches an
    uninterrupted fault-free run (rtol 1e-5 — dp reduction order
    differs across mesh sizes).  The watchdog kill, restart reasons and
    snapshot fallback must all be visible in ``supervisor.*`` stats,
    the exit history, and the kill-time flight dump.
    """
    import json

    import paddle_tpu as paddle
    from paddle_tpu.distributed.supervisor import (StepWatchdog,
                                                   TrainingSupervisor)
    from paddle_tpu.utils import monitor

    import jax
    if len(jax.devices()) < 8:
        print("FAIL: supervise scenario needs 8 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)
        return 1

    own_tmp = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_supervise_")
    was_static = paddle.in_static_mode() \
        if hasattr(paddle, "in_static_mode") else False

    def child_env(attempt):
        ndev = 8 if attempt < 2 else 4   # the replacement pod is smaller
        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={ndev}",
            "FLAGS_fault_spec": "",
        }
        if attempt == 0:
            # wedge one step for 600s: only the watchdog can clear it
            env["FLAGS_fault_spec"] = (
                f"executor.step_hang:count=1,after={hang_after},"
                f"action=sleep,secs=600")
        elif attempt == 1:
            # hard crash: no boundary save, no SystemExit — just gone
            env["FLAGS_fault_spec"] = (
                f"executor.run:count=1,after={crash_after},"
                f"action=exit,code=7")
        return env

    try:
        # -- reference: uninterrupted run on the full mesh ----------------
        from paddle_tpu.distributed.mesh import init_mesh
        import numpy as np
        paddle.enable_static()
        init_mesh({"dp": 8})
        main, loss, exe = _reshard_build()
        init_mesh({"dp": 8})
        feed = _reshard_feed()
        ref_losses = [float(exe.run(main, feed=feed,
                                    fetch_list=[loss])[0])
                      for _ in range(steps)]
        exe.close()
        paddle.static.reset_default_programs()
        if verbose:
            print(f"reference (mesh dp=8): {ref_losses}")

        # -- supervised chaos run -----------------------------------------
        from paddle_tpu.distributed.supervisor import SupervisorGaveUp
        monitor.stat_reset()
        sv = TrainingSupervisor(
            _supervised_entry, args=(workdir, steps, save_every),
            name="chaos",
            watchdog=StepWatchdog(multiplier=8.0, min_deadline_s=3.0,
                                  max_deadline_s=240.0),
            startup_timeout_s=240.0, hang_grace_s=2.0, poll_s=0.2,
            backoff_s=0.1, backoff_max_s=1.0,
            crash_window_s=600.0, crash_budget=4,
            child_env=child_env, workdir=workdir)
        try:
            result = sv.run()
        except SupervisorGaveUp as e:
            print(f"FAIL: supervisor gave up instead of self-healing: "
                  f"{e}", file=sys.stderr)
            return 1

        problems = []
        if not result.clean_exit:
            problems.append("supervised job did not end cleanly")
        if result.attempts != 3:
            problems.append(f"expected exactly 3 incarnations "
                            f"(hang, crash, finish), got "
                            f"{result.attempts}")
        reasons = [r["reason"] for r in result.exit_history]
        if not reasons or reasons[0] != "hang":
            problems.append(f"first restart reason {reasons[:1]} != "
                            f"'hang' (watchdog kill)")
        if len(reasons) < 2 or "crash(exit=7)" not in reasons[1]:
            problems.append(f"second restart reason {reasons[1:2]} != "
                            f"crash(exit=7)")

        # supervisor decisions must be observable in monitor stats
        stats = monitor.all_stats()
        if stats.get("supervisor.hang_kills", 0) < 1:
            problems.append("supervisor.hang_kills stat missing")
        if stats.get("supervisor.restarts", 0) != 2:
            problems.append(f"supervisor.restarts="
                            f"{stats.get('supervisor.restarts', 0)}, "
                            f"expected 2")
        if stats.get("supervisor.starts", 0) != 3:
            problems.append(f"supervisor.starts="
                            f"{stats.get('supervisor.starts', 0)}, "
                            f"expected 3")

        # the kill-time flight dump names the restart reason
        kill_dump = os.path.join(workdir, "supervisor_kill_a0.json")
        if not os.path.exists(kill_dump):
            problems.append("watchdog kill left no flight dump")
        else:
            with open(kill_dump) as f:
                box = json.load(f)
            if box.get("reason") != "supervisor.hang":
                problems.append(f"flight dump reason "
                                f"{box.get('reason')!r} != "
                                f"'supervisor.hang'")
            extra = box.get("extra") or {}
            if extra.get("restart_reason") != "hang" \
                    or extra.get("attempt") != 0:
                problems.append("flight dump extra lacks the annotated "
                                "restart reason/attempt")

        # the step log proves the resume path: three incarnations, the
        # last one on 4 devices resuming from a NONZERO snapshot step
        resumes, rows = [], {}
        with open(os.path.join(workdir, "steps.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "resume":
                    resumes.append(rec)
                else:
                    rows[rec["step"]] = rec   # last write wins
        if len(resumes) != 3:
            problems.append(f"expected 3 resume markers, got "
                            f"{len(resumes)}: {resumes}")
        else:
            if resumes[0]["step"] != 0 or resumes[0]["devices"] != 8:
                problems.append(f"first incarnation should start fresh "
                                f"on 8 devices: {resumes[0]}")
            if resumes[1]["devices"] != 8 or resumes[1]["step"] <= 0:
                problems.append(f"post-hang incarnation should resume "
                                f"a step snapshot on 8 devices: "
                                f"{resumes[1]}")
            if resumes[2]["devices"] != 4 or resumes[2]["step"] \
                    <= resumes[1]["step"]:
                problems.append(f"post-crash incarnation should "
                                f"reshard-resume on 4 devices past the "
                                f"previous snapshot: {resumes[2]}")
        if verbose:
            print(f"resumes: {resumes}")
            print(f"exit history: {result.exit_history}")

        # loss-trajectory parity with the fault-free run
        missing = [s for s in range(steps) if s not in rows]
        if missing:
            problems.append(f"steps never completed: {missing}")
        else:
            got = [rows[s]["loss"] for s in range(steps)]
            try:
                np.testing.assert_allclose(got, ref_losses, rtol=1e-5)
            except AssertionError as e:
                problems.append(
                    f"supervised loss trajectory diverged from the "
                    f"fault-free run: {e}")
            if verbose:
                print(f"supervised:  {got}")

        if problems:
            for p in problems:
                print(f"FAIL: {p}", file=sys.stderr)
            return 1
        print("chaos supervise OK: injected hang watchdog-killed "
              "(SIGTERM->SIGKILL) and resumed from a step snapshot; "
              "injected crash restarted onto mesh dp=4 via reshard "
              "restore; loss trajectory matches the fault-free run "
              "with zero manual intervention")
        return 0
    finally:
        if not was_static:
            paddle.disable_static()
        import paddle_tpu.static as _st
        _st.reset_default_programs()
        if own_tmp:
            shutil.rmtree(workdir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Registry chaos (ISSUE 19): the multi-model control plane under fire —
# a live weight swap on one model, an unload/reload of the other mid-
# traffic, and a supervised two-model replica crash ride-through
# ---------------------------------------------------------------------------

def _registry_serving_entry(prefix_a, prefix_b, port, state_file,
                            stop_file):
    """Supervised two-model registry replica (module-level so spawn can
    pickle it).  Binds the HTTP plane not-ready with a ModelRegistry,
    loads + warms both models, marks ready.  The FIRST incarnation
    hard-crashes about a second after going ready — with the parent's
    clients routing to both models — so the supervisor must restart it
    and the replacement must reload BOTH models before traffic
    recovers."""
    import threading
    import time

    from paddle_tpu import serving

    reg = serving.ModelRegistry(max_inflight=64)
    srv = serving.ServingServer(None, port=port, ready=False,
                                registry=reg).start()
    kw = {"max_batch_size": 8, "batch_timeout_ms": 5.0}
    reg.load("modelA", prefix_a, engine_kwargs=dict(kw))
    reg.load("modelB", prefix_b, engine_kwargs=dict(kw))
    srv.mark_ready()
    if not os.path.exists(state_file):
        with open(state_file, "w") as f:
            f.write("1")

        def _die():
            time.sleep(1.0)
            os._exit(9)         # a hard replica crash, mid-traffic
        threading.Thread(target=_die, daemon=True).start()
    while not os.path.exists(stop_file):
        time.sleep(0.05)
    srv.close()
    reg.close(timeout=10.0)


def registry_main(requests=16, clients=2, verbose=False, workdir=None,
                  supervised=True):
    """Two-model control-plane gate; returns 0 on success, 1 on failure.

    Part one (in-process, HTTP clients routing by model name): a
    :class:`~paddle_tpu.serving.ModelRegistry` serves ``modelA``
    (inference + generation engines) and ``modelB`` (inference) behind
    one :class:`ServingServer` while client threads hammer both.
    Under that fire: (1) a WeightWatcher hot-swaps modelA's inference
    weights — every A response must be bitwise-correct for exactly one
    published version and B's responses must never move; (2) modelB is
    unloaded mid-traffic — in-flight B requests finish bitwise, later
    ones get a clean :class:`UnknownModel` (the HTTP 404), never a hang
    — then reloaded, after which B serves bitwise again.  Final gates:
    zero hot-path recompiles across the swap, zero stranded futures,
    modelA's unload reports its generation page pool fully reclaimed,
    and the registry counters saw the unknown-model window.

    Part two (``supervised=True``): a two-model registry replica under
    a :class:`ServingSupervisor` hard-crashes mid-traffic; the
    supervisor restarts it, the replacement reloads BOTH models, and
    clients ride through on the reconnect path with post-restart
    responses bitwise for each model."""
    import threading
    import time

    from paddle_tpu import inference, serving
    from paddle_tpu.serving.hotswap import WeightWatcher, publish_weights
    from paddle_tpu.utils import monitor
    from paddle_tpu.utils.checkpoint import SnapshotStore

    own_tmp = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_registry_")
    problems = []
    monitor.stat_reset()

    # -- per-(model, version) bitwise references ---------------------------
    prefix_a0 = _scaled_artifact(1.0, workdir, "a0")
    prefix_a1 = _scaled_artifact(0.25, workdir, "a1")
    prefix_b = _scaled_artifact(0.5, workdir, "b")
    preds = {k: inference.create_predictor(inference.Config(p))
             for k, p in (("a0", prefix_a0), ("a1", prefix_a1),
                          ("b", prefix_b))}
    rng = np.random.RandomState(23)
    reqs = [(rng.randint(-8, 9, (rng.randint(1, 5), 8)) / 4.0)
            .astype(np.float32) for _ in range(requests)]
    refs = {k: [np.asarray(p.run([x])[0]) for x in reqs]
            for k, p in preds.items()}
    prompts = [rng.randint(0, 32, rng.randint(1, 9)).tolist()
               for _ in range(4)]
    budgets = [int(rng.randint(3, 7)) for _ in prompts]
    ref_gen = serving.GenerationEngine(make_dyadic_lm(), num_slots=4,
                                       page_size=4, max_context=64)
    ref_gen.warmup()
    gen_refs = [ref_gen.generate_sync(prompts[i], timeout=60,
                                      max_new_tokens=budgets[i],
                                      temperature=0.7, seed=i)
                for i in range(len(prompts))]
    ref_gen.close()

    # -- the registry under test -------------------------------------------
    reg = serving.ModelRegistry(max_inflight=64)
    eng_a = serving.InferenceEngine(preds["a0"], max_batch_size=8,
                                    batch_timeout_ms=5.0,
                                    max_queue=8 * requests, name="modelA")
    eng_a.warmup()
    gen_a = serving.GenerationEngine(make_dyadic_lm(), num_slots=4,
                                     page_size=4, max_context=64,
                                     max_queue=256, name="modelA")
    gen_a.warmup()
    store = SnapshotStore(os.path.join(workdir, "weights_a"))
    watcher = WeightWatcher(store, engine=eng_a, poll_s=0.05).start()
    reg.register("modelA", engine=eng_a, generation=gen_a,
                 watcher=watcher, weight=2.0)
    eng_b = serving.InferenceEngine(preds["b"], max_batch_size=8,
                                    batch_timeout_ms=5.0,
                                    max_queue=8 * requests, name="modelB")
    eng_b.warmup()
    reg.register("modelB", engine=eng_b)
    srv = serving.ServingServer(None, port=0, registry=reg).start()

    stop = threading.Event()
    a_out, b_out, g_out = [], [], []

    def a_client(idx):
        c = serving.Client(srv.url, model="modelA", timeout=30)
        k = idx
        while not stop.is_set():
            i = k % len(reqs)
            k += clients
            try:
                got = c.predict([reqs[i]])
                a_out.append((i, np.asarray(got[0], dtype=np.float32)))
            except Exception as e:  # noqa: BLE001 - gated below
                a_out.append((i, e))

    def b_client(idx):
        c = serving.Client(srv.url, model="modelB", timeout=30)
        k = idx
        while not stop.is_set():
            i = k % len(reqs)
            k += clients
            try:
                got = c.predict([reqs[i]])
                b_out.append((i, np.asarray(got[0], dtype=np.float32)))
            except Exception as e:  # noqa: BLE001 - gated below
                b_out.append((i, e))
            time.sleep(0.01)

    def g_client(idx):
        c = serving.Client(srv.url, model="modelA", timeout=60)
        k = idx
        while not stop.is_set():
            i = k % len(prompts)
            k += clients
            try:
                toks = c.generate(prompts[i],
                                  max_new_tokens=budgets[i],
                                  temperature=0.7, seed=i)
                g_out.append((i, toks))
            except Exception as e:  # noqa: BLE001 - gated below
                g_out.append((i, e))

    admin = serving.Client(srv.url, timeout=60)
    threads = [threading.Thread(target=f, args=(c,), daemon=True)
               for f in (a_client, b_client, g_client)
               for c in range(clients)]
    for t in threads:
        t.start()
    b_unknown_window = []
    try:
        time.sleep(0.4)                     # fire on (A=v0, B)

        # -- (1) live weight swap on modelA, B must not move -------------
        publish_weights(store, 1, artifact_prefix=prefix_a1)
        deadline = time.monotonic() + 60
        while watcher.version < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        if watcher.version != 1:
            problems.append(f"modelA swap not applied within 60s "
                            f"(last_error={watcher.last_error})")
        for i in range(3):
            got = admin.predict([reqs[i]], model="modelA")
            if not np.array_equal(np.asarray(got[0], np.float32),
                                  refs["a1"][i]):
                problems.append(f"modelA settled response {i} not "
                                f"bitwise at version 1")
            got = admin.predict([reqs[i]], model="modelB")
            if not np.array_equal(np.asarray(got[0], np.float32),
                                  refs["b"][i]):
                problems.append(f"modelB response {i} moved during "
                                f"modelA's swap")
        time.sleep(0.3)                     # fire on (A=v1, B)

        # -- (2) unload modelB mid-traffic, then reload ------------------
        mark = len(b_out)
        summary = admin.unload_model("modelB")
        if not summary.get("engine_drained"):
            problems.append(f"modelB unload did not drain cleanly: "
                            f"{summary}")
        t0 = time.monotonic()
        while time.monotonic() - t0 < 1.0:
            time.sleep(0.05)                # window where B is gone
        b_unknown_window = [r for _, r in b_out[mark:]
                            if isinstance(r, serving.UnknownModel)]
        if not b_unknown_window:
            problems.append("no B request saw a clean UnknownModel "
                            "while the model was unloaded")
        admin.load_model("modelB", prefix_b,
                         engine_kwargs={"max_batch_size": 8,
                                        "batch_timeout_ms": 5.0})
        reload_mark = len(b_out)
        time.sleep(0.4)                     # fire on the reloaded B
        post = [(i, r) for i, r in b_out[reload_mark:]
                if not isinstance(r, Exception)]
        if not post:
            problems.append("no B request succeeded after the reload")
    finally:
        stop.set()
        for t in threads:
            t.join(30)
        watcher.stop()
        srv.close()

    # -- part-one gates ----------------------------------------------------
    for i, res in a_out:
        if isinstance(res, Exception):
            problems.append(f"modelA request {i} failed under fire: "
                            f"{type(res).__name__}: {res}")
        elif not (np.array_equal(res, refs["a0"][i])
                  or np.array_equal(res, refs["a1"][i])):
            problems.append(f"modelA request {i}: response matches "
                            f"neither published version (a swap tore "
                            f"a batch)")
    clean_b = (serving.UnknownModel, serving.EngineClosed)
    for i, res in b_out:
        if isinstance(res, Exception):
            if not isinstance(res, clean_b):
                problems.append(f"modelB request {i} failed uncleanly "
                                f"under churn: {type(res).__name__}: "
                                f"{res}")
        elif not np.array_equal(res, refs["b"][i]):
            problems.append(f"modelB request {i} not bitwise")
    for i, res in g_out:
        if isinstance(res, Exception):
            problems.append(f"generation request {i} failed under "
                            f"fire: {type(res).__name__}: {res}")
        elif list(res) != list(gen_refs[i]):
            problems.append(f"generation request {i} tokens differ "
                            f"from the serial reference")
    if len(a_out) < 5 or len(g_out) < 2:
        problems.append(f"fire too thin: {len(a_out)} A requests, "
                        f"{len(g_out)} generations")

    # final teardown through the registry: stranded futures and page
    # reclamation are asserted from the unload summaries themselves
    summary_a = reg.unload("modelA", timeout=60)
    if not summary_a.get("pages_reclaimed", False):
        problems.append(f"modelA unload leaked pages: "
                        f"{summary_a.get('page_pool')}")
    stats_a = eng_a.stats()
    if stats_a["recompiles_after_warmup"] != 0:
        problems.append(f"modelA hot path recompiled "
                        f"{stats_a['recompiles_after_warmup']}x across "
                        f"the swap")
    if stats_a["counters"].get("closed_stranded", 0):
        problems.append(f"{stats_a['counters']['closed_stranded']} "
                        f"modelA futures stranded at close")
    gen_stats = gen_a.stats()
    if gen_stats["counters"]["pages_allocated"] \
            != gen_stats["counters"]["pages_freed"]:
        problems.append(
            f"page accounting: "
            f"{gen_stats['counters']['pages_allocated']} allocated vs "
            f"{gen_stats['counters']['pages_freed']} freed")
    if monitor.get_stat("registry.unknown_model") < 1:
        problems.append("registry.unknown_model never counted the "
                        "unload window")
    reg.close(timeout=30.0)
    if verbose:
        print(f"registry fire: {len(a_out)} A + {len(b_out)} B + "
              f"{len(g_out)} gen requests, "
              f"{len(b_unknown_window)} clean 404s in the unload "
              f"window, swap v{watcher.version}, "
              f"counters={reg.stats()['counters']}")

    # -- part two: supervised two-model replica crash ----------------------
    if supervised and not problems:
        problems.extend(_registry_supervised(prefix_a0, prefix_b,
                                             refs, reqs, workdir,
                                             verbose))

    if own_tmp:
        shutil.rmtree(workdir, ignore_errors=True)
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    print("chaos registry OK: modelA hot-swapped under two-model fire "
          "(bitwise per version, B unmoved), modelB unloaded mid-"
          "traffic (clean 404s, drained, no stranded futures) and "
          "reloaded, pages reclaimed, and a crashed two-model replica "
          "restarted with clients riding through")
    return 0


def _registry_supervised(prefix_a, prefix_b, refs, reqs, workdir,
                         verbose):
    """Part two of :func:`registry_main`: the supervised two-model
    replica crash.  Returns a list of failure strings."""
    import socket
    import threading
    import time

    from paddle_tpu import serving
    from paddle_tpu.distributed import ServingSupervisor
    from paddle_tpu.utils import monitor

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    url = f"http://127.0.0.1:{port}"
    state_file = os.path.join(workdir, "reg_sv_state")
    stop_file = os.path.join(workdir, "reg_sv_stop")

    sv = ServingSupervisor(
        _registry_serving_entry,
        args=(prefix_a, prefix_b, port, state_file, stop_file),
        name="regchaos", health_url=f"{url}/healthz",
        ready_poll_s=0.1, probe_timeout_s=2.0, ready_fail_budget=50,
        hang_deadline_s=300.0, startup_timeout_s=240.0, poll_s=0.1,
        backoff_s=0.1, backoff_max_s=0.5,
        crash_window_s=600.0, crash_budget=3,
        child_env={"JAX_PLATFORMS": "cpu"}, workdir=workdir)
    box = {}

    def run_sv():
        try:
            box["result"] = sv.run()
        except Exception as e:  # noqa: BLE001 - surfaced below
            box["error"] = e

    svt = threading.Thread(target=run_sv, daemon=True)
    svt.start()

    def wait_ready(deadline_s):
        deadline = time.monotonic() + deadline_s
        c = serving.Client(url, timeout=5, reconnect_backoff_s=0.05)
        while time.monotonic() < deadline:
            try:
                if c.healthz().get("ready"):
                    return True
            except Exception:  # noqa: BLE001 - replica not up yet
                pass
            time.sleep(0.1)
        return False

    successes, failures = [], []
    b_stop = threading.Event()

    def b_client(idx, model, ref_key):
        c = serving.Client(url, model=model, timeout=10,
                           reconnect_backoff_s=0.1)
        k = idx
        while not b_stop.is_set():
            i = k % len(reqs)
            k += 2
            try:
                got = c.predict([reqs[i]])
                successes.append((model, ref_key, i,
                                  np.asarray(got[0], np.float32)))
            except Exception as e:  # noqa: BLE001 - gated below
                failures.append((model, i, e))
            time.sleep(0.01)

    out = []
    try:
        if not wait_ready(240.0):
            return ["supervised two-model replica never became ready"]
        clients = [threading.Thread(target=b_client,
                                    args=(n, m, rk), daemon=True)
                   for n, (m, rk) in enumerate((("modelA", "a0"),
                                                ("modelB", "b")))]
        for t in clients:
            t.start()
        deadline = time.monotonic() + 120
        while monitor.get_stat("supervisor.serving.restarts") < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        if monitor.get_stat("supervisor.serving.restarts") < 1:
            b_stop.set()
            return ["two-model replica crash never triggered a "
                    "supervised restart"]
        if not wait_ready(240.0):
            b_stop.set()
            return ["restarted two-model replica never became ready "
                    "again"]
        # post-restart: fresh client, serial bitwise pass on BOTH models
        c = serving.Client(url, timeout=10)
        for model, key in (("modelA", "a0"), ("modelB", "b")):
            for i in range(3):
                got = c.predict([reqs[i]], model=model)
                arr = np.asarray(got[0], np.float32)
                if not np.array_equal(arr, refs[key][i]):
                    out.append(f"post-restart {model} response {i} "
                               f"not bitwise")
        try:
            c.predict([reqs[0]], model="nope")
            out.append("unknown model did not 404 on the restarted "
                       "replica")
        except serving.UnknownModel:
            pass
    finally:
        b_stop.set()
        with open(stop_file, "w") as f:
            f.write("1")
        sv.stop()
        svt.join(60)

    for model, key, i, arr in successes:
        if not np.array_equal(arr, refs[key][i]):
            out.append(f"{model} request {i} not bitwise during the "
                       f"ride-through")
    if not any(m == "modelA" for m, *_ in successes) \
            or not any(m == "modelB" for m, *_ in successes):
        out.append("ride-through traffic did not cover both models")
    if verbose:
        print(f"supervised ride-through: {len(successes)} successes, "
              f"{len(failures)} transient failures, restarts="
              f"{monitor.get_stat('supervisor.serving.restarts')}")
    return out


# ---------------------------------------------------------------------------
# Fleet observability (ISSUE 20): cross-process telemetry aggregation
# and one distributed /generate trace riding through a replica restart
# ---------------------------------------------------------------------------

def _fleet_gen_entry(port, state_file, stop_file):
    """Supervised generation replica for the fleet-observability gate
    (module-level so spawn can pickle it).  The spawn environment
    carries ``FLAGS_obs_spool_dir``/``FLAGS_obs_role`` staged by the
    supervisor, so this entrypoint spools telemetry with zero
    observability code of its own — which is exactly the property the
    gate exists to prove.  The FIRST incarnation hard-crashes
    (``os._exit``, no atexit: only already-spooled segments survive)
    about a second after going ready; the replacement serves until
    ``stop_file`` appears."""
    import threading
    import time

    from paddle_tpu import serving

    model = make_dyadic_lm()
    engine = serving.GenerationEngine(model, num_slots=4, page_size=4,
                                      max_context=64)
    srv = serving.ServingServer(None, port=port, generation=engine,
                                ready=False).start()
    engine.warmup()
    srv.mark_ready()
    if not os.path.exists(state_file):
        with open(state_file, "w") as f:
            f.write("1")

        def _die():
            time.sleep(1.0)
            os._exit(9)         # a hard replica crash, mid-traffic

        threading.Thread(target=_die, daemon=True).start()
    while not os.path.exists(stop_file):
        time.sleep(0.05)
    srv.close()
    engine.close()


def fleet_main(verbose=False, workdir=None):
    """Fleet-observability gate; returns 0 on success, 1 on failure.

    A :class:`ServingSupervisor`-managed generation replica (spooling
    telemetry via the staged ``FLAGS_obs_spool_dir``) hard-crashes
    mid-traffic and is restarted; a traffic thread with a PINNED trace
    id keeps issuing ``/generate`` requests through the outage.  Gates:

    * the spool holds per-process records for the parent AND both
      child incarnations (roles ``fleet-a0``/``fleet-a1``);
    * :func:`~paddle_tpu.observability.fleet.merged_chrome_trace`
      yields named, wall-time-aligned lanes for all of them, and the
      parent lane carries the supervisor ``restart`` event with the
      crash reason;
    * :func:`~paddle_tpu.observability.fleet.fleet_prometheus_text`
      labels every sample with ``{proc=...}``;
    * :func:`~paddle_tpu.observability.fleet.assemble_trace` stitches
      the pinned trace into ONE connected component spanning the
      parent pid and at least one server pid — the distributed span
      tree survives the process hop.
    """
    import json  # noqa: F401 - symmetry with sibling gates
    import socket
    import threading
    import time

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.core import flags
    from paddle_tpu.distributed import ServingSupervisor
    from paddle_tpu.observability import export as obs_export
    from paddle_tpu.observability import fleet as obs_fleet
    from paddle_tpu.utils import monitor

    own_tmp = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_fleet_")
    spool = os.path.join(workdir, "spool")
    problems = []
    old_flags = {k: flags.get_flag(k)
                 for k in ("obs_spool_dir", "obs_role",
                           "obs_export_interval_s")}
    paddle.set_flags({"obs_spool_dir": spool, "obs_role": "parent",
                      "obs_export_interval_s": 0.2})
    from paddle_tpu.core import obs_hook
    had_tracer = obs_hook._tracer is not None
    obs_export.install_exporter()
    monitor.stat_reset()

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    url = f"http://127.0.0.1:{port}"
    state_file = os.path.join(workdir, "fleet_state")
    stop_file = os.path.join(workdir, "fleet_stop")

    sv = ServingSupervisor(
        _fleet_gen_entry, args=(port, state_file, stop_file),
        name="fleet", health_url=f"{url}/healthz",
        ready_poll_s=0.1, probe_timeout_s=2.0, ready_fail_budget=50,
        hang_deadline_s=300.0, startup_timeout_s=240.0, poll_s=0.1,
        backoff_s=0.1, backoff_max_s=0.5,
        crash_window_s=600.0, crash_budget=3,
        child_env={"JAX_PLATFORMS": "cpu",
                   "FLAGS_obs_export_interval_s": "0.2"},
        workdir=workdir)
    box = {}

    def run_sv():
        try:
            box["result"] = sv.run()
        except Exception as e:  # noqa: BLE001 - surfaced below
            box["error"] = e

    svt = threading.Thread(target=run_sv, daemon=True)
    svt.start()

    def wait_ready(deadline_s):
        deadline = time.monotonic() + deadline_s
        c = serving.Client(url, timeout=5, reconnect_backoff_s=0.05)
        while time.monotonic() < deadline:
            try:
                if c.healthz().get("ready"):
                    return True
            except Exception:  # noqa: BLE001 - replica not up yet
                pass
            time.sleep(0.1)
        return False

    tid = "fleetgate"
    ok_counts = []
    b_stop = threading.Event()

    def traffic():
        c = serving.Client(url, timeout=10, reconnect_backoff_s=0.1,
                           trace_id=tid)
        while not b_stop.is_set():
            try:
                toks = c.generate([3, 5], max_new_tokens=3)
                ok_counts.append(len(toks))
            except Exception:  # noqa: BLE001 - outage window
                pass
            time.sleep(0.05)

    try:
        if not wait_ready(240.0):
            return _fleet_report(["supervised replica never became "
                                  "ready"], verbose)
        tt = threading.Thread(target=traffic, daemon=True)
        tt.start()
        deadline = time.monotonic() + 120
        while monitor.get_stat("supervisor.serving.restarts") < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        if monitor.get_stat("supervisor.serving.restarts") < 1:
            b_stop.set()
            return _fleet_report(["replica crash never triggered a "
                                  "supervised restart"], verbose)
        if not wait_ready(240.0):
            b_stop.set()
            return _fleet_report(["restarted replica never became "
                                  "ready again"], verbose)
        # at least one traced request must land on the NEW incarnation
        pre = len(ok_counts)
        deadline = time.monotonic() + 60
        while len(ok_counts) <= pre and time.monotonic() < deadline:
            time.sleep(0.1)
        b_stop.set()
        tt.join(30)
        if len(ok_counts) <= pre:
            problems.append("no /generate succeeded after the restart")
        with open(stop_file, "w") as f:
            f.write("1")
        svt.join(60)
        if "error" in box:
            problems.append(f"supervisor errored: {box['error']}")

        # -- spool: parent + BOTH child incarnations ----------------------
        exp = obs_export.get_exporter()
        if exp is not None:
            exp.flush()
        procs = obs_fleet.read_spool(spool)
        roles = {p["role"] for p in procs}
        for want in ("parent", "fleet-a0", "fleet-a1"):
            if want not in roles:
                problems.append(f"spool lacks a record for {want!r} "
                                f"(roles: {sorted(roles)})")
        corrupt = sum(p["corrupt"] for p in procs)
        if corrupt:
            problems.append(f"{corrupt} corrupt spool document(s)")

        # -- merged chrome trace: named aligned lanes + restart reason ----
        merged = obs_fleet.merged_chrome_trace(spool)
        evs = merged.get("traceEvents") or []
        lanes = {e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
        for want in ("parent", "fleet-a0", "fleet-a1"):
            if not any(ln.startswith(want + "-") for ln in lanes):
                problems.append(f"merged trace lacks a lane for "
                                f"{want!r} (lanes: {sorted(lanes)})")
        restarts = [e for e in evs if e.get("name") == "restart"
                    and "crash" in str((e.get("args") or {})
                                       .get("reason", ""))]
        if not restarts:
            problems.append("merged trace lacks the supervisor restart "
                            "event with the crash reason")
        if any(e.get("ts", 0) < 0 for e in evs):
            problems.append("merged trace has negative timestamps "
                            "(lane alignment broke)")

        # -- fleet Prometheus: every sample proc-labelled -----------------
        text = obs_fleet.fleet_prometheus_text(spool)
        bad = [ln for ln in text.splitlines()
               if ln and not ln.startswith("#") and 'proc="' not in ln]
        if bad:
            problems.append(f"fleet Prometheus samples without a proc "
                            f"label: {bad[:3]}")

        # -- the pinned trace is ONE component across the process hop -----
        asm = obs_fleet.assemble_trace(
            obs_fleet._merge_self(list(procs)), tid)
        if not asm["connected"]:
            problems.append(f"distributed trace not connected: {asm}")
        if len(asm["pids"]) < 2:
            problems.append(f"distributed trace never crossed a "
                            f"process boundary: pids={asm['pids']}")
    finally:
        b_stop.set()
        with open(stop_file, "w") as f:
            f.write("1")
        sv.stop()
        svt.join(60)
        obs_export.uninstall_exporter()
        if not had_tracer:          # install_exporter enabled it for us
            from paddle_tpu import observability as _obs
            _obs.disable()
        paddle.set_flags(old_flags)
        if own_tmp:
            shutil.rmtree(workdir, ignore_errors=True)

    if verbose and not problems:
        print(f"fleet gate: {len(ok_counts)} traced generates, "
              f"restarts={monitor.get_stat('supervisor.serving.restarts')}, "
              f"procs={sorted(roles)}, trace pids={asm['pids']}")
    return _fleet_report(problems, verbose)


def _fleet_report(problems, verbose):
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    if not problems:
        print("chaos fleet: parent + both incarnations spooled, lanes "
              "aligned, restart reason visible, pinned /generate trace "
              "connected across the replica restart")
    return 1 if problems else 0
