#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, TPU only: without the cell's chips it exits non-zero and
prints no result.  ``--rehearse`` drives the same code at the cell's tiny
``rehearsal`` sizes on whatever backend jax has (the CPU here) and
prints no result line either: a rehearsal has no device numbers.

Everything that belongs to one cell, configuration, traffic mix, model
family, runner or per-layer metric is a file found by its name; see
README.md.
"""
import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

T_PROCESS_START = time.time()

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def log(msg):
    print(msg, flush=True)


# ------------------------------------------------------- files by name --
def load_json(kind, name, root=HERE):
    path = os.path.join(root, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind, name, root=HERE):
    """benchmark/<kind>/<name>.py as a module; names may hold dots."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name, root=HERE, rehearse=False):
    cell = load_json("workloads", name, root)
    cfg = load_json("configs", cell["config"], root)
    mix = load_json("traffic", cell["traffic"], root)
    if rehearse:
        cfg = {**cfg, **cell["rehearsal"]["config"]}
        mix = {**mix, **mix["rehearsal"]}
        cell["check"] = {**cell["check"],
                         "limits": cell["rehearsal"]["limits"]}
    return cell, cfg, mix


def peak_of(kind, root=HERE):
    with open(os.path.join(root, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(known: {sorted(peaks)}); add it with its source")
    return peaks[kind]


def metric_entries(section, cell_name):
    """The entries of BENCHMARK.json's ``section`` that this cell reports."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]


# ------------------------------------------------ what jax compiled/ran --
class CompileLog:
    """Every executable jax builds or loads, as jax itself reports it
    (``jax.monitoring``): (function name, seconds) per compile, and how
    many the persistent cache answered.  Copy of chip_smoke.py's."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = []
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((kw.get("fun_name", "?"), float(secs)))

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return len(self.compiles), self.cache_hits

    def since(self, mark):
        new = self.compiles[mark[0]:]
        return new, sum(s for _, s in new), self.cache_hits - mark[1]


def executable_footprint(module_name):
    """Bytes the compiler laid out for the one live executable of that
    name: arguments + outputs - aliased + temporaries + code.  This is
    the step's peak: on this backend ``peak_bytes_in_use`` of
    ``memory_stats()`` covers live arrays only (PERF.md, PR 21), and what
    it has seen by the end of a run is the reference's arrays, which are
    not the program's."""
    import jax
    found = [ex for ex in jax.devices()[0].client.live_executables()
             if ex.hlo_modules()[0].name == module_name]
    if not found:
        raise AssertionError(f"no live executable named {module_name!r}")
    stats = [ex.get_compiled_memory_stats() for ex in found]
    return max(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes
               + m.generated_code_size_in_bytes for m in stats)


# ------------------------------------------------------------ the window --
class Spans:
    """The benchmark's own spans around the calls into the program, on
    the host clock (``ms``: name -> durations); in a traced run also
    written into the profiler's trace (``bench:<name>``), where the idle
    gaps are attributed."""

    def __init__(self, annotate):
        self.ms = {}
        self._annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name):
        import jax
        ann = (jax.profiler.TraceAnnotation("bench:" + name)
               if self._annotate else contextlib.nullcontext())
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.ms.setdefault(name, []).append(
                    (time.perf_counter() - t0) * 1000)


def drive_window(runner, state, ring, seconds, spans, max_steps=None,
                 first_batch=0):
    """Keep one step in flight: dispatch step i+1, then wait on the loss
    of step i and stamp the clock.  The window opens when the last
    warm-up step completes with the first counted step already queued
    behind it, and closes at the first step boundary at or after
    ``seconds`` (or after ``max_steps``): every counted step ran wholly
    inside it.  Returns (window seconds, losses on the device, stamps)."""
    def dispatch(i):
        ids, labels = ring[i % len(ring)]
        with spans("feed"):
            fed = runner.feed(state, ids, labels)
        with spans("dispatch"):
            return runner.dispatch(state, fed)

    # a full garbage collection over the million objects that importing
    # and tracing left behind stalls the host for 0.1-0.7 s, long enough to
    # starve the device (PERF.md, PR 23): none runs inside the window
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        return _drive(dispatch, first_batch, seconds, spans, max_steps)
    finally:
        gc.enable()
        gc.unfreeze()


def _drive(dispatch, i, seconds, spans, max_steps):
    pending = dispatch(i)
    nxt = dispatch(i + 1)
    i += 2
    pending.block_until_ready()
    t0 = time.perf_counter()
    pending, losses, stamps = nxt, [], []
    while True:
        nxt = dispatch(i)
        i += 1
        with spans("wait"):
            pending.block_until_ready()
        stamps.append(time.perf_counter() - t0)
        losses.append(pending)
        pending = nxt
        if stamps[-1] >= seconds or (max_steps and len(stamps) >= max_steps):
            break
    pending.block_until_ready()       # drain; not counted
    return stamps[-1], losses, stamps


# ---------------------------------------------------- shared preparation --
def place_cache():
    """jax's persistent compile cache at the fixed place the program's
    own entry points use (``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``), every program cached, the small ones too:
    a warm run then compiles nothing."""
    import jax
    sys.path.insert(0, REPO)
    sys.path.insert(0, HERE)
    from paddle_tpu.core.xla_env import place_compile_cache
    cache_dir = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def load_parts(cell_name, root=HERE, rehearse=False):
    cell, cfg, mix = load_cell(cell_name, root, rehearse)
    return (cell, cfg, mix,
            load_module("models", cell["model"], root),
            load_module("reference", cfg["family"], root),
            load_module("runners", cell["runner"], root))


def seeded_inputs(cell, cfg, mix, ref, seed):
    """-> (ring of host batches, function that makes the weights)."""
    import traffic
    import weights
    ring = traffic.generate(mix, cfg["vocab_size"], seed)
    shapes = ref.param_shapes(cfg, cell["model_args"])
    round_to = None if cell["dtype"] == "float32" else cell["dtype"]
    return ring, weights.maker(seed, shapes, round_to)


# ------------------------------------------------------------- the check --
def follow_reference(check, ref, cell, cfg, mix, ring, theta0,
                     precision="float32"):
    """The plain reference through the cell's first steps."""
    return check.follow(
        ref, cfg, cell["model_args"], theta0,
        ring[:cell["check"].get("steps", 3)], cell["optimizer"],
        min(cell["check"]["reference_block_rows"], mix["batch"]), precision)


def follow_program(check, runner, state, cell, ring, theta0):
    """The timed object through the same steps, by the window's own feed
    and call; the same numbers read from its state."""
    got = {"losses": []}
    for i in range(cell["check"].get("steps", 3)):
        loss = runner.dispatch(state, runner.feed(state, *ring[i]))
        got["losses"].append(float(loss))
        if i == 0:
            got["grad_norms"], got["grad_samples"] = check.first_gradient(
                runner.moments(state), cell["optimizer"])
    got["update_norms"] = check.update_norms(runner.params_f32(state),
                                             theta0)
    return got


# ---------------------------------------------------------------- a run --
def run_cell(args, rehearse=False, root=HERE, runner=None):
    """Everything after the look for the chip.  Returns the result dict.
    ``runner`` lets a test put a broken one in the cell's place."""
    import jax

    cache_dir = place_cache()
    import check
    import trace_reduce

    clog = CompileLog()
    dev = jax.devices()[0]
    log(f"[run] cell {args.workload} seed {args.seed} on "
        f"{jax.device_count()} x {dev.device_kind}; compile cache {cache_dir}")
    cell, cfg, mix, model_mod, ref, own_runner = load_parts(
        args.workload, root, rehearse)
    runner = runner or own_runner
    ring, theta0 = seeded_inputs(cell, cfg, mix, ref, args.seed)

    # the reference goes first and is freed before the program's state is
    # made; its time is not part of setup_s
    before_reference_s = time.time() - T_PROCESS_START
    t_ref = time.perf_counter()
    want = follow_reference(check, ref, cell, cfg, mix, ring, theta0)
    reference_s = time.perf_counter() - t_ref
    log(f"[check] reference followed {len(want['losses'])} steps in "
        f"{reference_s:.1f} s (steps {want['step_seconds']}): "
        f"losses {want['losses']}")

    # one object: built here, checked on its first steps, then timed
    t_build = time.perf_counter()
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    t_follow = time.perf_counter()
    got = follow_program(check, runner, state, cell, ring, theta0)
    t_warm = time.perf_counter()
    n_follow = len(got["losses"])
    log(f"[check] program's first {n_follow} steps: losses {got['losses']}")
    numbers = check.compare(got, want)

    for i in range(cell["warm_steps"]):
        float(runner.dispatch(state, runner.feed(
            state, *ring[(n_follow + i) % len(ring)])))
    setup_compiles, compile_s, cache_hits = clog.since((0, 0))
    setup_s = time.time() - T_PROCESS_START - reference_s
    setup_parts = {"before the reference": before_reference_s,
                   "build": t_follow - t_build,
                   "checked steps": t_warm - t_follow,
                   "warm-up": time.perf_counter() - t_warm}

    # ---- the measured window
    tokens_per_step = mix["batch"] * mix["seq"]
    spans = Spans(bool(args.trace))
    trace_dir = None
    if args.trace:
        trace_dir = args.keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
        # the python tracer would add an event per call and slow the host
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    mark = clog.mark()
    try:
        window_s, losses, stamps = drive_window(
            runner, state, ring, args.seconds, spans,
            max_steps=cell["trace_steps"] if args.trace else None,
            first_batch=n_follow + cell["warm_steps"])
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    late = clog.since(mark)[0]
    losses = [float(x) for x in losses]
    steps = len(losses)
    rate = steps * tokens_per_step / window_s / cell["chips"]
    gaps = [b - a for a, b in zip([0.0] + stamps, stamps)]
    log(f"[window] {steps} steps in {window_s:.4f} s; step interval ms "
        f"median {statistics.median(gaps) * 1000:.3f} min "
        f"{min(gaps) * 1000:.3f} max {max(gaps) * 1000:.3f} (at step "
        f"{gaps.index(max(gaps))}); host spans ms, median and max: "
        + ", ".join(f"{k} {statistics.median(v):.2f} {max(v):.2f}"
                    for k, v in spans.ms.items()))

    # ---- correct
    correct = check.verdict(numbers, cell["check"]["limits"], log)
    finite = all(math.isfinite(x) for x in losses + got["losses"])
    log(f"[check] every loss finite: {finite}")
    correct = correct and finite
    if steps >= 10 and not args.trace:
        head, tail = statistics.mean(losses[:5]), statistics.mean(losses[-5:])
        log(f"[check] loss mean of last five {tail:.6f} against first five "
            f"{head:.6f}: {'fell' if tail < head else 'DID NOT FALL'}")
        correct = correct and tail < head
    log(f"[check] compiles inside the window: {len(late)} (limit 0) {late}")
    correct = correct and not late

    # ---- metrics
    peaks = None if rehearse else peak_of(dev.device_kind, root)
    footprint = executable_footprint(runner.EXECUTABLE)
    stats_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    log(f"[memory] step executable footprint {footprint} (reported); "
        f"memory_stats peak_bytes_in_use {stats_peak} (live arrays only, "
        "the reference's included)")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": footprint}
    result = {"correct": bool(correct), "attempted": steps, "failed": 0}
    if not args.trace:
        if peaks is not None:
            flops = model_mod.train_flops_per_token(cfg, mix["seq"])
            top = peaks["bf16_flops_per_s"]
            log(f"[window] MFU of the window {rate * flops / top * 100:.3f} "
                f"% ({flops:.6g} FLOPs a token, peak {top:.3g})")
        values = {"tokens_per_s_per_chip": rate, "setup_s": setup_s}
        entries = metric_entries("end_to_end", args.workload)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in entries}
    else:
        reduced = trace_reduce.reduce_planes(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir)),
            module=runner.EXECUTABLE)
        log(f"[trace] {reduced['module_runs']} executions of "
            f"{runner.EXECUTABLE} in a window of {reduced['window_s']:.4f} s, "
            f"device busy {reduced['busy_s']:.4f} s")
        ctx = {"trace": reduced, "log": log, "spans": spans.ms, "cell": cell,
               "cfg": cfg, "mix": mix, "model": model_mod,
               "runner": runner, "peaks": peaks,
               "compile": {"seconds": compile_s,
                           "compiles": len(setup_compiles),
                           "cache_hits": cache_hits}}
        result["metrics"] = {}
        for m in metric_entries("per_layer", args.workload):
            value = load_module("layer_metrics", m["name"], root).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    result["device"] = device
    slowest = sorted(setup_compiles, key=lambda c: -c[1])[:4]
    log(f"[setup] setup_s {setup_s:.3f} (reference's {reference_s:.3f} s "
        f"not counted); compiles {len(setup_compiles)} taking "
        f"{compile_s:.2f} s, {cache_hits} from the persistent cache; "
        f"slowest {[(n, round(t, 1)) for n, t in slowest]}; seconds in "
        + ", ".join(f"{k} {v:.1f}" for k, v in setup_parts.items()))
    runner.close(state)
    return result


def has_chips(cell_name):
    """Whether jax holds the TPU chips the cell asks for; says so on
    stderr if not.  A measurement never falls back to another backend."""
    import jax
    chips = load_json("workloads", cell_name)["chips"]
    devs = jax.devices()
    if devs[0].platform == "tpu" and len(devs) >= chips:
        return True
    print(f"benchmark: cell {cell_name} needs {chips} TPU chip(s); jax has "
          f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
    return False


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no result line")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: write the profile here and keep it")
    args = ap.parse_args(argv)

    if not args.rehearse and not has_chips(args.workload):
        return 2
    result = run_cell(args, rehearse=args.rehearse)
    if args.rehearse:
        log(f"[rehearsal] ran to the end; correct={result['correct']}; "
            "no result line: a rehearsal has no device numbers")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
