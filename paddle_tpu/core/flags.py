"""Global flags registry.

TPU-native equivalent of the reference's gflags tier (reference:
paddle/fluid/platform/flags.cc:33-577, surfaced to Python through
pybind/global_value_getter_setter.cc as ``core.globals()`` and
``paddle.set_flags``).  Flags may also be seeded from the environment with the
``FLAGS_`` prefix, matching the reference's env passthrough.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass
class _FlagDef:
    name: str
    default: Any
    help: str
    parser: Callable[[str], Any]


_registry: Dict[str, _FlagDef] = {}
_values: Dict[str, Any] = {}
_lock = threading.Lock()


def _parse_bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


def define_flag(name: str, default: Any, help: str = "") -> None:
    if isinstance(default, bool):
        parser: Callable[[str], Any] = _parse_bool
    elif isinstance(default, int):
        parser = int
    elif isinstance(default, float):
        parser = float
    else:
        parser = str
    with _lock:
        _registry[name] = _FlagDef(name, default, help, parser)
        env = os.environ.get(f"FLAGS_{name}")
        if env is not None:
            _values[name] = parser(env)
        else:
            _values.setdefault(name, default)


def get_flag(name: str) -> Any:
    if name not in _registry:
        raise KeyError(f"Unknown flag: {name}")
    return _values[name]


def set_flags(flags: Dict[str, Any]) -> None:
    """paddle.set_flags parity."""
    for k, v in flags.items():
        k = k.replace("FLAGS_", "")
        if k not in _registry:
            raise KeyError(f"Unknown flag: {k}")
        with _lock:
            _values[k] = v


def get_flags(names=None) -> Dict[str, Any]:
    if names is None:
        return dict(_values)
    if isinstance(names, str):
        names = [names]
    return {n.replace("FLAGS_", ""): get_flag(n.replace("FLAGS_", "")) for n in names}


# ---------------------------------------------------------------------------
# Core flag set (subset of reference platform/flags.cc relevant on TPU)
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False,
            "Scan every op output for NaN/Inf (reference: flags.cc:44).")
define_flag("use_pallas_kernels", True,
            "Use Pallas fused kernels (flash attention etc.) when on TPU.")
define_flag("static_verify", False,
            "Run static.analysis verification (def-use, cross-program "
            "leaks, shape/dtype drift, name collisions, dead code) on "
            "each Program before its first compile, and record file:line "
            "anchors for every op at build time.  Off by default: "
            "verification adds one eval_shape re-trace per op.")
define_flag("shard_verify", False,
            "Run the shardcheck SPMD safety passes (static/analysis/"
            "shardcheck: plan coverage & divisibility, collective "
            "choreography, device-varying taint, wire-byte audit) once "
            "per (program, sharding-plan fingerprint) before the first "
            "sharded compile.  A plan/config the Executor would refuse "
            "at compile time then fails preflight as a structured "
            "GraphVerificationError carrying the same cause string.  "
            "Compile keys are unchanged, so the 0-recompile contract "
            "holds with the flag on or off.")
define_flag("static_anchors", False,
            "Record a file:line source anchor on every op "
            "Program.record appends — the cheap subset of "
            "FLAGS_static_verify (one frame walk per recorded op at "
            "build time, no per-run verification), so "
            "Program.analyze() reports and lint/analyze CLIs carry "
            "user-source anchors.")
define_flag("static_donate", True,
            "Donate parameter/optimizer buffers of the static Executor's "
            "compiled train step (jax.jit donate_argnums), updating "
            "weights in place run-to-run.  Aliasing-safe: any array a "
            "user obtains through Parameter.data is copied out of the "
            "donated set before the next run.  Turn off to keep every "
            "step's input buffers alive (debugging / buffer archaeology).")
define_flag("profiler_sync_ops", False,
            "Profiler op timing blocks on device completion per op "
            "(block_until_ready) instead of timing only the async host "
            "dispatch.  Accurate per-op device cost attribution at the "
            "price of serializing the pipeline; default off.  Also "
            "settable per-Profiler via Profiler(sync_ops=True).")
define_flag("fault_spec", "",
            "Deterministic fault-injection spec (paddle_tpu.testing.fault"
            " grammar: 'point_glob:p=...,count=...,exc=...;...').  Armed "
            "from the environment at import; after set_flags() call "
            "testing.fault.arm_from_flags().  Empty = injector disarmed "
            "(zero overhead).")
define_flag("fault_seed", 0,
            "Seed for the fault injector's RNG — a chaos run with the "
            "same spec+seed replays the same fault sequence.")
define_flag("fs_retry_times", 4,
            "Max attempts (1 initial + retries) for a filesystem op that "
            "fails with a transient error (ShellFS always; other "
            "registered filesystems when wrapped in RetryingFS).")
define_flag("fs_retry_backoff_s", 0.2,
            "Base exponential-backoff delay between fs retries; attempt "
            "n sleeps ~base*2^n plus up to 25% jitter, capped at 10s.")
define_flag("fs_retry_deadline_s", 60.0,
            "Wall-clock budget across all retry attempts of one fs op; "
            "past it the op gives up even with attempts remaining.")
define_flag("dataloader_timeout", 120,
            "Seconds a DataLoader iterator waits on worker results "
            "before declaring the pool stalled (DataLoader(timeout=) "
            "overrides per loader).")
define_flag("dataloader_batch_retries", 3,
            "Times one batch may be re-enqueued after DataLoader worker "
            "deaths before the epoch fails for good.")
define_flag("dataloader_respawn_backoff_s", 0.2,
            "Base delay before respawning a dead DataLoader worker when "
            "deaths are clustering: the first death in the crash-loop "
            "window respawns immediately, the Nth waits "
            "~base*2^(N-2) (capped by "
            "FLAGS_dataloader_respawn_backoff_max_s).  Keeps a flapping "
            "node from burning the batch retry budget in a tight "
            "respawn loop.")
define_flag("dataloader_respawn_backoff_max_s", 5.0,
            "Cap on the per-respawn backoff delay.")
define_flag("dataloader_crashloop_window_s", 30.0,
            "Sliding window for DataLoader worker crash-loop detection.")
define_flag("dataloader_crashloop_budget", 6,
            "Worker deaths tolerated inside the crash-loop window; one "
            "more raises WorkerCrashLoop with the full exit_history "
            "instead of respawning again (fast-fail for a poisoned "
            "dataset or a dying node).")
define_flag("mesh_replace_warn_only", False,
            "Downgrade the error raised when init_mesh/set_mesh would "
            "replace a live mesh that compiled programs still hold "
            "shardings against (distributed/mesh.py) to a warning.  The "
            "stale executables keep the OLD device placement — only set "
            "this when you know every holder is about to be rebuilt.")
define_flag("checkpoint_keep_max", 2,
            "Snapshots retained per checkpoint dir (keep_checkpoint_max; "
            ">=2 keeps a fallback for corrupt-latest recovery).")
define_flag("inference_pad_policy", "bucket",
            "Predictor.run on a batch size with no compiled variant: "
            "'bucket' pads the leading dim to the smallest compiled/"
            "declared bucket (next power of two when none fits) and "
            "slices outputs back — zero recompiles after warmup; 'none' "
            "compiles a fresh variant per batch size (legacy).")
define_flag("serving_dispatch_retries", 2,
            "InferenceEngine: batch dispatch attempts after a failure "
            "before the batch's requests are failed (inference is pure, "
            "so a flaked dispatch is safely retried).")
define_flag("serving_decode_retries", 2,
            "GenerationEngine: decode-step attempts after a failure "
            "before the in-flight sequences are failed (the step is "
            "functional over the KV pool, so a flaked dispatch is "
            "safely retried).")
define_flag("metrics_dump_path", "",
            "When set, training appends periodic monitor-metrics "
            "snapshots (stats + histograms, one JSON object per line) "
            "to this JSONL file — Model.fit auto-attaches the "
            "hapi.callbacks.MetricsDump callback; other loops can call "
            "observability.dump_metrics() directly.")
define_flag("flight_recorder_path", "",
            "Default dump path for the crash flight recorder "
            "(observability.install_flight_recorder).  On EnforceError, "
            "an exception escaping Executor.run, SIGTERM or an "
            "unhandled exception, the last tracer events + a full "
            "metrics snapshot are written here atomically.")
define_flag("perf_sample_every", 16,
            "Runtime performance observatory (observability.enable_perf)"
            ": fence (block_until_ready) and sample device memory on "
            "every Nth step per compile identity.  Unsampled steps stay "
            "fully async — only host-side timestamps are taken — so the "
            "donated dispatch pipeline is never serialized.  <=0 "
            "disables fencing entirely (host anatomy only).")
define_flag("perf_chip", "",
            "Roofline chip spec used to turn the cost model's predicted "
            "FLOPs/traffic into a predicted step time for the drift "
            "tracker (static/analysis/cost.CHIP_SPECS key).  Empty = "
            "auto: 'cpu' on the CPU backend, by device_kind on a TPU.")
define_flag("pallas_interpret", False,
            "Let the automatic Pallas-tier selectors (the static "
            "Executor's epilogue-fusion pass, the fused Adam update, "
            "the paged-attention decode hook) pick Pallas kernels OFF "
            "TPU, running them in interpret mode.  Interpret mode is "
            "orders of magnitude slower than jnp — this exists so "
            "tests and tools/kernel_smoke.py exercise the exact "
            "TPU kernel dataflow under JAX_PLATFORMS=cpu, never as a "
            "CPU performance path.  On a real TPU backend the tier "
            "needs only FLAGS_use_pallas_kernels.")
define_flag("xla_latency_hiding", False,
            "Enable XLA's latency-hiding scheduler by appending the "
            "backend's scheduler flags to XLA_FLAGS at import, BEFORE "
            "backend initialisation (core/xla_env.py; set it as the "
            "FLAGS_xla_latency_hiding environment variable — a "
            "set_flags() call after jax's backend exists is too late "
            "and is ignored with a warning).  With it on, the per-"
            "bucket grad_comm collectives (strategy.grad_comm.overlap="
            "'auto') are split into async start/done pairs the "
            "scheduler hoists across backward compute — comm hides "
            "behind backward instead of adding to it; without it, "
            "overlap='auto' falls back to the ppermute-chunked ring "
            "lowering on TPU/GPU.  TPU/GPU only: the CPU backend has "
            "no such scheduler (and rejects unknown XLA flags "
            "fatally), so CPU processes never get flags appended and "
            "auto keeps the fused per-bucket collectives there — a "
            "serial backend overlaps nothing; force overlap='ring' to "
            "exercise the chunked lowering on CPU.")
define_flag("anomaly_sentry", False,
            "Fuse the data-plane anomaly sentry into the static "
            "Executor's compiled train step: per-bucket gradient "
            "finiteness checks + grad-norm stats collapse to one scalar "
            "anomaly flag (psum'd over the dp axis so every replica "
            "takes the same branch), and the parameter/optimizer/"
            "step-counter/error-feedback update is applied through a "
            "jnp.where select — a flagged step is a bitwise no-op "
            "instead of a silent weight corruption.  The production "
            "analog of the reference's FLAGS_check_nan_inf (also "
            "opt-in), but one reduction per existing bucket view "
            "instead of per kernel launch: negligible next to real "
            "model math, measurable on micro-benchmarks.  "
            "Supervised production training should run with it on.  "
            "Flipping it recompiles (the executable either carries the "
            "sentry or it doesn't; attribution names the flip).")
define_flag("anomaly_skip_budget", 2,
            "AnomalyPolicy: consecutive sentry-flagged (skipped) steps "
            "tolerated before escalating — first past the budget "
            "quarantines the blamed batch, the next escalates to a "
            "snapshot rollback.")
define_flag("anomaly_rollback_budget", 1,
            "AnomalyPolicy: snapshot rollbacks attempted before the "
            "policy gives up and raises AnomalyEscalation (handing the "
            "incarnation to the TrainingSupervisor's restart path).")
define_flag("anomaly_spike_window", 32,
            "AnomalyPolicy rolling window (clean steps) for the "
            "loss-spike detector's median.")
define_flag("anomaly_spike_factor", 10.0,
            "AnomalyPolicy: a finite loss above median * factor over "
            "the rolling window counts as an anomaly (catches finite "
            "corruption — e.g. a bitflipped wire payload — that the "
            "non-finite sentry cannot flag).  <= 0 disables the "
            "spike detector.")
define_flag("compile_cache_dir", "",
            "Persistent AOT executable cache directory.  When set, the "
            "compiling layers that serve traffic (inference Predictor "
            "buckets, GenerationEngine decode/prefill variants, the "
            "static Executor's single-device inference step) serialize "
            "each compiled executable through core/compile_cache.py and "
            "reload it on the next cold start — a respawned replica "
            "skips XLA entirely for warm buckets (cold-start-to-first-"
            "token cut >5x; serve_smoke gates it).  Entries are keyed "
            "by the recompile-attribution signature plus a jax/jaxlib/"
            "backend/topology stamp, so a version or device change "
            "invalidates cleanly (compile_cache.rejects) instead of "
            "loading a stale executable.  We serialize ourselves via "
            "jax.experimental.serialize_executable, whatever jax's own "
            "persistent compilation cache is set to.  Empty = "
            "disabled (no filesystem traffic).")
define_flag("metrics_dump_max_mb", 0.0,
            "Size-based rotation threshold for the FLAGS_metrics_dump_"
            "path JSONL file: before each append, a file at/above this "
            "many MiB is atomically renamed to <path>.1 (existing "
            "rotated files shift up, the oldest beyond "
            "FLAGS_metrics_dump_keep is deleted) so long-lived replicas "
            "never grow one unbounded flight file.  <= 0 disables "
            "rotation (legacy unbounded append).")
define_flag("metrics_dump_keep", 3,
            "Rotated metrics-dump files retained (<path>.1 .. <path>.N) "
            "when FLAGS_metrics_dump_max_mb rotation triggers.")
define_flag("obs_spool_dir", "",
            "Fleet telemetry spool directory.  When set, this process "
            "installs the per-process telemetry exporter "
            "(observability.export) at import: checksummed metrics "
            "snapshots and tracer-ring segments are spooled atomically "
            "to <dir>/<role>-<pid>/ for the fleet aggregator "
            "(observability.fleet) to merge into one timeline / one "
            "Prometheus view.  Supervisors stage this into child "
            "environments automatically, so supervised children and "
            "serving replicas export with zero code changes.  Empty = "
            "off: instrumented sites pay one module-attribute "
            "None-check (the core.obs_hook contract).")
define_flag("obs_role", "",
            "Role label for this process's telemetry spool "
            "(<role>-<pid> directory name and the {proc=...} Prometheus "
            "label).  Supervisors stage '<name>-a<attempt>' for each "
            "child incarnation; empty = 'proc'.")
define_flag("obs_export_interval_s", 5.0,
            "Seconds between telemetry spool flushes.  The exporter's "
            "daemon thread flushes on this cadence; instrumented hot "
            "paths (Executor._run, the serving dispatchers) also tick "
            "it so a busy process that dies between timer fires still "
            "leaves a recent spool.  Ticks inside the interval are "
            "rate-limited to one time check.")
