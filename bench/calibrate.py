"""Readings that set a cell's rate and its correctness limits, made once
on the chip when the cell is defined; the benchmark's own runs never
make them.

    python bench/calibrate.py knee --workload W --seed N --seconds S --rates R1 R2 ...
    python bench/calibrate.py limits --workload W --seconds S --seeds N1 N2 ...

``knee`` runs an open-loop cell at each offered rate, in one process, and
prints what each rate did to the latency tails and how many requests
failed to finish: the knee is the highest rate whose backlog does not
grow. ``limits`` runs the cell on each seed and prints, per seed, the
program's widest gap to the reference and the lower-precision control's
widest gap at the same positions: the lower and the upper readings that
a limit lies between.
"""

from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, configure_jax, run_cell, say


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("knee", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", type=int, nargs="*", default=())
    ap.add_argument("--rates", type=float, nargs="*", default=())
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    configure_jax(args.rehearsal)
    rows = []
    if args.what == "knee":
        for r in args.rates:
            res = run_cell(ROOT, args.workload, args.seed, args.seconds,
                           False, args.rehearsal, rate_per_s=r,
                           strict=False)
            row = {"rate_per_s": r, "attempted": res["attempted"],
                   "failed": res["failed"], "correct": res["correct"],
                   **res["window"],
                   **{k: v["value"] for k, v in res["metrics"].items()}}
            say("knee " + json.dumps(row))
            rows.append(row)
    else:
        for seed in args.seeds:
            res = run_cell(ROOT, args.workload, seed, args.seconds, False,
                           args.rehearsal, control=True)
            row = {"seed": seed, "program": res["checks"]["gap_max"]["value"],
                   "control": res["control"]["gap_max"],
                   "attempted": res["attempted"], "failed": res["failed"]}
            say("limits " + json.dumps(row))
            rows.append(row)
    print(json.dumps({"what": args.what, "workload": args.workload,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
