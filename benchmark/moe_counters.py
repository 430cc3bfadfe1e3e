"""The expert layers' device counters, as three per-layer metrics read
them: what the compiled step counted about its routers' load, every step
of the process (checked, warm-up and window), from the program's
registry.

The program keeps these counts in ``TrainStep``'s carry on the device and
publishes them into ``paddle_tpu.utils.monitor`` only when asked
(``observability.read_device_counters``: one ``device_get`` that waits
for the newest dispatched step; the metrics are read after the window, so
no window sees it).  A counter ``name`` whose step value is
``[calls, ...]`` (one entry an expert layer, in the model's order)
arrives as ``name.steps``, ``name.total.<call>[.<i>]`` (summed over the
steps) and ``name.last.<call>[.<i>]`` (the newest step's).

The names below are the yardstick's copy of the program's vocabulary
(``paddle_tpu/observability/scopes.py``), as ``scope_reduce``'s are: a
program without them (one from before the counters, or a step with no
expert layer) reads as ``None`` with a log line, never as zero.
"""
import statistics

CHUNK_ASSIGNMENTS = "moe.chunk_assignments"   # [calls, chunks]: held
                                              # assignments of each chunk (row)
FULL_BUFFER_CHUNKS = "moe.full_buffer_chunks"  # [calls]: chunks over `small`
EXPERT_LOAD = "moe.expert_load"               # [calls, held]
FULLEST_EXPERT_LOAD = "moe.fullest_expert_load"  # [calls]: max of the above,
                                              # which a sum over steps loses
SMALL_ROWS = "moe.small_buffer_rows"          # trace time: rows a chunk's
FULL_ROWS = "moe.full_buffer_rows"            # gathers walk on either branch


def registry(log, who):
    """The program's registry after a read of the device counters; None,
    with a log line, where the program has no such reader."""
    try:
        from paddle_tpu.observability import read_device_counters
    except ImportError:
        log(f"[{who}] this program has no device counters "
            "(paddle_tpu.observability.read_device_counters): nothing read")
        return None
    from paddle_tpu.utils import monitor
    read_device_counters()
    return monitor.all_stats()


def table(stats, name, part):
    """``name.<part>.<call>[.<i>]`` of the registry as rows a call."""
    prefix = f"{name}.{part}."
    cells = {tuple(map(int, key[len(prefix):].split("."))): value
             for key, value in stats.items() if key.startswith(prefix)}
    rows = {}
    for index in sorted(cells):
        rows.setdefault(index[0], []).append(cells[index])
    return [rows[call] for call in sorted(rows)]


def loads(ctx, who):
    """-> {"steps", "assigned" / "assigned_last" [calls][chunks], "full"
    [calls], "load" [calls][held], "fullest" [calls], "small_rows",
    "full_rows"} over every step read so far; None with a log line where
    there is nothing to read.  Read once a run and kept in ``ctx``, as
    ``scope_reduce.table`` keeps its rows."""
    if "moe_loads" not in ctx:
        ctx["moe_loads"] = _loads(ctx["log"], who)
    return ctx["moe_loads"]


def _loads(log, who):
    stats = registry(log, who)
    if stats is None:
        return None
    steps = stats.get(f"{CHUNK_ASSIGNMENTS}.steps")
    if not steps or SMALL_ROWS not in stats:
        log(f"[{who}] no step of this process counted an expert layer "
            f"({CHUNK_ASSIGNMENTS}.steps = {steps}): nothing read")
        return None
    got = {"steps": steps,
           "assigned": table(stats, CHUNK_ASSIGNMENTS, "total"),
           "assigned_last": table(stats, CHUNK_ASSIGNMENTS, "last"),
           "full": [row[0] for row in
                    table(stats, FULL_BUFFER_CHUNKS, "total")],
           "load": table(stats, EXPERT_LOAD, "total"),
           "fullest": [row[0] for row in
                       table(stats, FULLEST_EXPERT_LOAD, "total")],
           "small_rows": stats[SMALL_ROWS], "full_rows": stats[FULL_ROWS]}
    last = [n for row in got["assigned_last"] for n in row]
    log(f"[moe_counters] {steps} steps, {len(got['assigned'])} expert "
        f"layers a step, {len(got['assigned'][0])} chunks a layer; held "
        f"assignments a chunk in the newest step: least {min(last)}, mean "
        f"{statistics.mean(last):.1f}, most {max(last)}; the fullest chunk "
        f"is {max(last) / got['small_rows'] * 100:.2f} % of the small "
        f"buffer's {got['small_rows']} rows (full buffer "
        f"{got['full_rows']}); chunks over it since the start "
        f"{sum(got['full'])}")
    return got
