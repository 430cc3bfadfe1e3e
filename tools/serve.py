#!/usr/bin/env python
"""Serve a saved inference artifact over HTTP.

Load the artifact (the ``.pdmodel`` prefix written by ``paddle.jit.save``
/ ``paddle.static.save_inference_model``), warm up the batch buckets so
the hot path never compiles, and serve:

    python tools/serve.py /path/to/model_prefix --port 8000

    curl localhost:8000/healthz
    curl localhost:8000/metrics
    curl -X POST localhost:8000/predict \
         -H 'Content-Type: application/json' \
         -d '{"inputs": [[[0.1, 0.2, 0.3, 0.4]]]}'

``inputs`` is a list of per-input arrays (or a name->array dict), each
with a leading batch dim.  SIGINT/SIGTERM drain in-flight work before
exit.  See README "Serving" for bucket/padding and backpressure
semantics.

The HTTP plane binds *before* warmup with readiness down: ``/healthz``
answers 503 + ``Retry-After`` (``"warming"``) until the buckets are
compiled, then flips to 200 — a supervisor or load balancer holds
traffic instead of timing out on a compiling replica.  With
``--weights-dir`` a :class:`~paddle_tpu.serving.WeightWatcher` polls
that :class:`~paddle_tpu.utils.checkpoint.SnapshotStore` directory and
hot-swaps newly published, digest-verified weights into the live
engine with zero downtime and zero recompiles (see README "Serving
operations").

**Multi-model mode**: ``--models manifest.json`` starts the full
control plane instead — every entry in the manifest is loaded into a
:class:`~paddle_tpu.serving.ModelRegistry` (each model warms before
its name becomes routable; readiness flips when ALL manifest models
are ready), requests route by the JSON ``"model"`` field / ``X-Model``
header, and ``/admin/models`` loads/unloads/aliases more models at
runtime.  Manifest shape::

    {"models": {
        "prod-resnet": {"artifact": "/path/prefix",
                         "weights_dir": "/path/snapshots",
                         "aliases": ["prod"], "weight": 2.0,
                         "rest_shapes": [[3, 224, 224]]},
        "canary":      {"artifact": "/other/prefix"}},
     "default": "prod-resnet",
     "max_inflight": 128,
     "quotas": {"tenant-a": {"rate": 50, "burst": 100}}}

``max_inflight`` is the weighted-fair-queuing pool; ``quotas`` are
per-tenant token buckets.  With ``FLAGS_compile_cache_dir`` set the
per-model warmups deserialize previously compiled buckets instead of
paying XLA again (see README "Multi-model control plane").
"""
from __future__ import annotations

import argparse
import os
import signal
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[1],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("model", nargs="?", default=None,
                    help="artifact path prefix (as passed to jit.save / "
                         "save_inference_model); omit with --models")
    ap.add_argument("--models", default=None, metavar="MANIFEST.json",
                    help="multi-model manifest (see module docstring): "
                         "serve a ModelRegistry with per-model engines, "
                         "admin endpoints, WFQ and quotas instead of a "
                         "single engine")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--max-batch-size", type=int, default=32)
    ap.add_argument("--batch-timeout-ms", type=float, default=2.0)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request in-queue deadline")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated batch buckets to pad to "
                         "(default: powers of two up to max batch)")
    ap.add_argument("--rest-shape", action="append", default=None,
                    metavar="D0,D1,...",
                    help="per-input shape without the batch dim, once per "
                         "input (only needed when the artifact's non-batch "
                         "dims are symbolic)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip AOT warmup (first requests will compile)")
    ap.add_argument("--weights-dir", default=None,
                    help="SnapshotStore directory to watch for hot-swap "
                         "weight snapshots (publish_weights); new "
                         "digest-verified versions swap in with zero "
                         "downtime")
    ap.add_argument("--weights-poll-s", type=float, default=2.0,
                    help="meta-poll cadence of the weight watcher")
    ap.add_argument("--verbose", action="store_true",
                    help="log every HTTP request")
    args = ap.parse_args(argv)

    from paddle_tpu.core.xla_env import place_compile_cache
    place_compile_cache()

    from paddle_tpu import inference, serving

    if args.models:
        return _serve_registry(args)
    if not args.model:
        ap.error("need an artifact prefix (or --models MANIFEST.json)")

    config = inference.Config(args.model)
    predictor = inference.create_predictor(config)
    buckets = ([int(b) for b in args.buckets.split(",")]
               if args.buckets else None)
    engine = serving.InferenceEngine(
        predictor, max_batch_size=args.max_batch_size,
        batch_timeout_ms=args.batch_timeout_ms, max_queue=args.max_queue,
        default_deadline_ms=args.deadline_ms, buckets=buckets)
    # bind the HTTP plane first, not-ready: liveness probes answer (503
    # "warming" + Retry-After) while the buckets compile, and readiness
    # flips only when the hot path is warm
    srv = serving.ServingServer(engine, host=args.host, port=args.port,
                                verbose=args.verbose, ready=False).start()
    rest = ([tuple(int(d) for d in s.split(","))
             for s in args.rest_shape] if args.rest_shape else None)
    if not args.no_warmup:
        n = engine.warmup(rest_shapes=rest)
        print(f"warmed {len(engine.buckets)} buckets "
              f"{engine.buckets} -> {n} compiled variants", flush=True)
    srv.mark_ready()

    watcher = None
    if args.weights_dir:
        watcher = serving.WeightWatcher(
            args.weights_dir, engine=engine,
            poll_s=args.weights_poll_s, rest_shapes=rest).start()
        print(f"watching {args.weights_dir} for weight snapshots",
              flush=True)

    stop = {"sig": None}

    def _on_signal(signum, frame):
        stop["sig"] = signum
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _on_signal)
    print(f"serving {args.model} on {srv.url}  "
          f"(POST /predict, GET /healthz, GET /metrics)", flush=True)
    try:
        signal.pause()
    except KeyboardInterrupt:
        pass
    finally:
        print("draining...", flush=True)
        if watcher is not None:
            watcher.stop()
        srv.close()
        engine.drain(timeout=30.0)
        engine.close()
        c = engine.stats()["counters"]
        print(f"served {c['responses']}/{c['requests']} requests in "
              f"{c['batches']} batches (shed={c['shed']}, "
              f"expired={c['deadline_expired']}, "
              f"weight_swaps={c['weight_swaps']})", flush=True)
    return 0


def _serve_registry(args) -> int:
    """--models mode: a ModelRegistry behind one HTTP plane."""
    import json

    from paddle_tpu import serving

    with open(args.models) as f:
        manifest = json.load(f)
    models = manifest.get("models") or {}
    if not models:
        print(f"manifest {args.models} has no models", file=sys.stderr)
        return 2

    reg = serving.ModelRegistry(
        max_inflight=manifest.get("max_inflight"),
        default_model=manifest.get("default"))
    for tenant, q in (manifest.get("quotas") or {}).items():
        reg.set_quota(tenant, float(q["rate"]), q.get("burst"))

    # bind first, not-ready: the readiness gate holds traffic while
    # every manifest model loads + warms (each name becomes routable
    # the moment ITS warmup finishes — a late model never blocks an
    # early one from serving admin/metrics probes)
    srv = serving.ServingServer(None, host=args.host, port=args.port,
                                verbose=args.verbose, ready=False,
                                registry=reg).start()
    for name, spec in models.items():
        rest = ([tuple(int(d) for d in s) for s in spec["rest_shapes"]]
                if spec.get("rest_shapes") else None)
        entry = reg.load(
            name, spec["artifact"],
            weights_dir=spec.get("weights_dir"),
            weights_poll_s=float(spec.get("weights_poll_s", 2.0)),
            aliases=spec.get("aliases", ()),
            weight=float(spec.get("weight", 1.0)),
            warmup=not args.no_warmup, rest_shapes=rest,
            engine_kwargs={
                "max_batch_size": args.max_batch_size,
                "batch_timeout_ms": args.batch_timeout_ms,
                "max_queue": args.max_queue,
                "default_deadline_ms": args.deadline_ms,
            })
        print(f"loaded {name} <- {spec['artifact']} "
              f"(weight={entry.weight}, "
              f"aliases={list(spec.get('aliases', ()))})", flush=True)
    srv.mark_ready()

    stop = {"sig": None}

    def _on_signal(signum, frame):
        stop["sig"] = signum
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _on_signal)
    print(f"serving {len(reg.models())} models {reg.models()} on "
          f"{srv.url}  (POST /predict {{\"model\": ...}}, "
          f"GET/POST /admin/models)", flush=True)
    try:
        signal.pause()
    except KeyboardInterrupt:
        pass
    finally:
        print("draining...", flush=True)
        srv.close()
        reg.close(timeout=30.0)
        c = reg.stats()["counters"]
        print(f"routed {c['requests']} requests across "
              f"{c['loads']} loads / {c['unloads']} unloads "
              f"(wfq_shed={c['wfq_shed']}, quota_shed={c['quota_shed']}, "
              f"unknown_model={c['unknown_model']})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
