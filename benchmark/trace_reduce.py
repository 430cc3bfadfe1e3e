"""From a profiler trace (``.xplane.pb``) to device-op intervals and the
numbers the per-layer metrics read: busy union, per-name sums, idle gaps.

Reads with ``jax.profiler.ProfileData`` alone.  ``reduce_planes`` works
on plain tuples, so a test can hand it a trace made by hand.
"""
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MOSAIC = 'custom_call_target="tpu_custom_call"'


def short_name(text):
    """The trace prints an op as its whole HLO line; this keeps the
    instruction's name and its result type: ``fusion.12 bf16[64,512]``.
    A custom call keeps its target too."""
    name, _, rest = text.partition(" = ")
    out = name.lstrip("%")
    kind = rest.split("{", 1)[0].split(" ", 1)[0]
    if kind and not kind.startswith("("):      # a tuple's type is long
        out += " " + kind
    if "custom_call_target=" in rest:
        out += " " + rest.split("custom_call_target=", 1)[1].split(
            ",")[0].strip('"')
    return out


def module_name(text):
    """``jit_step_fn(1323...)`` -> ``jit_step_fn``."""
    return text.split("(", 1)[0]


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def load(path):
    """-> {"devices": {plane: {"ops": [(name, start_ns, dur_ns, stats)],
    "modules": [...]}}, "host": [(name, start_ns, dur_ns)]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns),
                                     dict(ev.stats)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench:"):
                        host.append((ev.name[len("bench:"):],
                                     int(ev.start_ns),
                                     int(ev.duration_ns)))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def union(intervals):
    """Merged, sorted (start, end) list of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events):
    """name -> summed self time: an op's duration less what the ops
    nested inside it on the same line cover (a ``while`` holds its body's
    ops), so that the sums over names add up to the busy time."""
    sums, stack = {}, []
    for text, start, dur, *_ in sorted(events, key=lambda e: (e[1], -e[2])):
        name, end = short_name(text), start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            sums[parent[0]] -= min(end, parent[1]) - start
        sums[name] = sums.get(name, 0) + dur
        stack.append((name, end))
    return sums


def host_span_at(host, t):
    """The benchmark span the host was in at time ``t``: the innermost
    one that covers it, else "outside"."""
    best = None
    for name, start, dur in host:
        if start <= t < start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "outside"


def reduce_planes(trace, module=None):
    """``trace`` as ``load`` returns it.  The window runs from the start
    of the first execution of ``module`` (an executable's name, as
    ``module_name`` gives it) to the end of its last one on each device;
    without ``module``, from the first device op to the last.  Times in
    seconds.  Busy and idle are averaged over the devices."""
    per_dev = []
    for plane, dev in sorted(trace["devices"].items()):
        ops = dev["ops"]
        if not ops:
            continue
        runs = [m for m in dev["modules"]
                if module and module_name(m[0]) == module]
        w0 = min(s for _, s, _, *_ in runs or ops)
        w1 = max(s + d for _, s, d, *_ in runs or ops)
        inside = [e for e in ops if e[1] >= w0 and e[1] + e[2] <= w1]
        merged = union([(s, s + d) for _, s, d, *_ in inside])
        busy = sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        per_dev.append({"plane": plane, "window_ns": w1 - w0,
                        "busy_ns": busy, "sums": self_times(inside),
                        "runs": len(runs),
                        "events": inside, "gaps": gaps,
                        })
    if not per_dev:
        raise ValueError("the trace holds no device operation")
    n = len(per_dev)
    sums = {}
    for d in per_dev:
        for name, ns in d["sums"].items():
            sums[name] = sums.get(name, 0) + ns / n
    first = per_dev[0]
    gaps = sorted(first["gaps"], key=lambda g: g[0] - g[1])
    return {
        "window_s": sum(d["window_ns"] for d in per_dev) / n / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_dev) / n / 1e9,
        "op_seconds": {k: v / 1e9 for k, v in sums.items()},
        "events": first["events"],
        "module_runs": first["runs"],
        "idle_gaps": [(host_span_at(trace["host"], (s + e) // 2),
                       (e - s) / 1e9) for s, e in gaps],
        "devices": n,
    }


def mosaic_seconds_per_run(reduced):
    """Device seconds in Mosaic custom calls (the program's Pallas
    kernels) per execution of the window's executable; None where the
    window holds none."""
    ns = sum(dur for text, _, dur, *_ in reduced["events"] if MOSAIC in text)
    return ns / 1e9 / reduced["module_runs"] if ns else None


def breakdown(reduced, top_ops=10, top_gaps=5):
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top_ops]],
            "idle_gaps": [[k, v] for k, v in
                          reduced["idle_gaps"][:top_gaps]]}
