#!/usr/bin/env python3
"""Read, at a cell's own size, the two numbers every limit of its check
is set from: what sound runs of the program give, and what the control
gives.  Not part of a benchmark run; run on the chip when a cell or its
check is defined or changed (PERF.md gives the readings).

    python3 benchmark/control.py --workload <cell> \\
        --program-seeds 1,2,... --control-seeds 7,8,9

The control is the plain reference itself, computed one precision below
the cell's (``check.control`` in the cell's file) and put in the
program's place.  No measured window: a training cell's readings need
none.  ``--rehearse`` as in run.py.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None, help="write the readings here")
    args = ap.parse_args(argv)

    if not args.rehearse and not harness.has_chips(args.workload):
        return 2
    harness.place_cache()
    import check
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        args.workload, rehearse=args.rehearse)
    readings = {"program": {}, "control": {}}

    def seeds(text):
        return [int(x) for x in text.split(",") if x]

    for seed in seeds(args.control_seeds):
        ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed)
        want = harness.follow_reference(check, ref, cell, cfg, mix, ring,
                                        theta0)
        low = harness.follow_reference(check, ref, cell, cfg, mix, ring,
                                       theta0, cell["check"]["control"])
        numbers = check.compare(low, want)
        readings["control"][seed] = {k: v[0] for k, v in numbers.items()}
        harness.log(f"[control {cell['check']['control']}] seed {seed}: "
                    + json.dumps(readings["control"][seed]))
        ok = check.verdict(numbers, cell["check"]["limits"], harness.log)
        harness.log(f"[control] seed {seed}: within every limit: {ok} "
                    "(a control must not be)")
        del want, low
        gc.collect()

    for seed in seeds(args.program_seeds):
        ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed)
        want = harness.follow_reference(check, ref, cell, cfg, mix, ring,
                                        theta0)
        state = runner.build(cell, cfg, model_mod, theta0(), mix)
        got = harness.follow_program(check, runner, state, cell, ring,
                                     theta0)
        numbers = check.compare(got, want)
        readings["program"][seed] = {k: v[0] for k, v in numbers.items()}
        harness.log(f"[program] seed {seed}: "
                    + json.dumps(readings["program"][seed])
                    + f" worst leaves {[v[1] for v in numbers.values()]}")
        runner.close(state)
        del state, got, want
        gc.collect()

    for side in ("program", "control"):
        rows = readings[side].values()
        if rows:
            for name in next(iter(rows)):
                vals = [r[name] for r in rows]
                harness.log(f"[summary] {side} {name}: min {min(vals):.6g} "
                            f"max {max(vals):.6g} over {len(vals)} seeds")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
