"""Readings for the limits of ``correct``: the program's, and a control's,
over several seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --control kv_int8|int8|fp8|none --seeds 1 2 3

Each seed is a whole run of the cell (set-up, window at the cell's load,
comparison).  ``--control`` puts a step of lower precision in the
program's place (``bench/run.py``'s ``CONTROLS``): ``kv_int8`` serves
with the program's own int8 KV cache; ``int8`` and ``fp8`` read the plain
reference on int8 (W8A8) or fp8 operands at the same prompts and served
tokens.  ``correct`` is decided by the benchmark's own comparison from
the control's numbers, and has to come out false.  ``none`` runs the
program as the benchmark does: its readings set the lower end of each
limit, the control's the upper (see PERF.md).  Prints one JSON line per
seed and a summary.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", required=True,
                    choices=R.CONTROLS + ("none",))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    control = None if args.control == "none" else args.control
    rows = []
    for s in args.seeds:
        try:
            run = R.run_cell(R.ROOT, args.workload, s, args.seconds, False,
                             t_process=time.monotonic(), control=control)
        except R.NoAccelerator as e:
            print(f"[control] {e}", file=sys.stderr)
            return 3
        out, g = run.result, run.gaps or {}
        row = {"seed": s, "control": args.control, "correct": out["correct"],
               **{k: v["value"] for k, v in out["check"].items()},
               "program_gap": g.get("gap"),
               "program_mean_gap": g.get("mean_gap"),
               "reference_control_gap": g.get("control_gap"),
               "reference_control_mean_gap": g.get("control_mean_gap"),
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "control": args.control,
               "correct": [r["correct"] for r in rows]}
    for k in ("logit_gap", "mean_logit_gap", "program_gap",
              "program_mean_gap", "reference_control_gap",
              "reference_control_mean_gap"):
        vals = [r[k] for r in rows if r.get(k) is not None]
        if vals:
            summary[k] = [min(vals), max(vals)]
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
