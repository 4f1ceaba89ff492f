#!/usr/bin/env python3
"""Readings that the limits of the correctness check are set from, in one process.

    python3 benchmarks/chip/readings.py --workload <cell> --seeds 1 2 3 ... \
        --control-seeds 1 2 3 --seconds 3 [--out FILE]

For each of ``--seeds``: set up, warm up, run the cell's closed loop for
``--seconds`` and compare the sampled answers with the reference, exactly
as a run does (the program's readings).  For each of ``--control-seeds``:
put the reference, in the precision below the configuration's, in the
program's place and compare it the same way (the control's readings).
Needs the chip, as a run does.  The benchmark's own runs never run this.
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from yardstick import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    import jax

    from yardstick.compiles import tpu_devices

    tpu_devices(int(cell["cell"]["chips"]))
    harness.enable_cache(jax)
    rows = []
    for kind, seeds in (("program", args.seeds), ("control", args.control_seeds)):
        for seed in seeds:
            work = cell["entry"].Work(cell["config"], cell["traffic"], seed)
            if kind == "program":
                work.warm()
                calls = harness.closed_loop(work, args.seconds, 0)[2]
            else:
                calls = list(range(int(cell["traffic"]["check_sample"]) + 1))
            checks = harness.check(work, cell["traffic"], calls, seed, control=kind == "control")
            row = {"kind": kind, "seed": seed, "calls": len(calls),
                   "readings": {n: v for n, v, _ in checks}}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        pathlib.Path(args.out).write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
