"""Find a cell's knee: the same run at several fixed arrival rates.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 1 2 3

Each rate is a whole run with the mix's arrival rate replaced (an MMPP
keeps its burst-to-quiet ratio), without the comparison.  The knee is the
highest rate at which the queue does not grow over the window; the cells
store their rates in their traffic files, and PERF.md records the sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench, _, _, traffic = R.load_cell(R.ROOT, args.workload)
    base = traffic["arrivals"]
    for rate in args.rates:
        arr = dict(base, rate=rate)
        if "rate_hi" in base:
            arr["rate_hi"] = rate * base["rate_hi"] / base["rate"]
        try:
            run = R.run_cell(R.ROOT, args.workload, args.seed, args.seconds,
                             False, t_process=time.monotonic(),
                             traffic_override={"arrivals": arr}, check=False)
        except R.NoAccelerator as e:
            print(f"[sweep] {e}", file=sys.stderr)
            return 3
        ttft = [run.times[r][0] - t for r, t in run.arrival.items()
                if run.times.get(r) and run.in_window(run.times[r][0])]
        row = {"rate": rate, "arrivals": len(run.arrival),
               "queue_at_close": run.counters["queue_at_close"],
               "finished_in_window": run.counters["finished_in_window"],
               "first_tokens": len(ttft),
               "ttft_p50_s": float(np.median(ttft)) if ttft else None}
        for m in bench["end_to_end"]:
            v = R.load_module(R.ROOT / "bench" / "e2e"
                              / f"{m['name']}.py").read(run)
            row[m["name"]] = None if v is None else float(v)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
