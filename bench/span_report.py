"""One benchmark run with the engine's spans read out: where the host
time of the decode loop goes.

    python3 bench/span_report.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--spans on|follow] [--out FILE]

Runs ``bench/run.py``'s cell as the benchmark does and prints one JSON
object: the run's metrics and counters, and

- with ``--spans on`` (the default) the engine's recorder is on for the
  whole window (``SpanRecorder.enable``): over the window, seconds and
  counts per span name, the controller's split, host time per decode
  step and its parts, queue wait and admission time, the stall of each
  plan that moved heads, the longest controller intervals with the host
  pauses (``host.gc``, ``host.compile``) inside them, and how late the
  generator submitted each admitted arrival (``t_submit`` less its
  schedule); with ``--spans follow`` the recorder is on only while the
  profiler traces, as the program ships it;
- with ``--trace 1`` also the traced seconds' idle gaps named by the
  program's spans (the ten longest, and the sum per name), and device self
  time per named scope.

The reductions are ``bench/program_spans.py``'s, the ones the metric
readers apply to the trace.  ``--out FILE`` appends the object to FILE as
well.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_PROCESS = time.monotonic()

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import program_spans as ps  # noqa: E402
from bench import run as R  # noqa: E402


def _q(values, qs=(50, 90, 99)):
    import numpy as np
    if not len(values):
        return None
    return {f"p{q}": float(np.percentile(values, q)) for q in qs}


def _sum_by_name(evs) -> dict:
    out: dict = {}
    for e in evs:
        out[e.name] = out.get(e.name, 0.0) + e.dur
    return out


def ring_summary(ring, window, arrival) -> dict:
    """The recorder's spans that lie inside ``window`` (program clock)."""
    t_a, t_b = window
    evs = ps.ordered(ps.Ev(s.name, s.t0, s.t1, {"rid": s.rid, "arg": s.arg})
                     for s in ring if t_a <= s.t0 and s.t1 <= t_b)
    if not evs:
        return {}
    win = t_b - t_a
    secs = _sum_by_name(evs)
    count: dict = {}
    for e in evs:
        count[e.name] = count.get(e.name, 0) + 1
    split = ps.controller_split(evs)
    host_ms = [1e3 * h for h in ps.host_step_seconds(evs)]
    steps = [(i, e) for i, e in enumerate(evs) if e.name == "sched.step"]
    parts = ("kv.mount", "model.decode_dispatch", "sched.sample",
             "sched.emit")
    in_steps = _sum_by_name(e for i, _ in steps for e in ps.inside(evs, i)
                            if e.name in parts)
    admits = ps.admissions(evs)
    ivs = sorted(((i, e) for i, e in enumerate(evs)
                  if e.name == "ctl.interval"), key=lambda x: -x[1].dur)
    out = {
        "window_s": win,
        "seconds": dict(sorted(secs.items())),
        "count": dict(sorted(count.items())),
        "ctl_share_pct": {k: 100.0 * v / win for k, v in split.items()},
        "assign_of_interval_pct": (100.0 * split.get("ctl.assign", 0.0)
                                   / split["ctl.interval"]
                                   if split.get("ctl.interval") else None),
        "host_step_ms": (dict(_q(host_ms), mean=sum(host_ms) / len(host_ms))
                         if host_ms else None),
        "host_step_parts_ms": {k: 1e3 * in_steps.get(k, 0.0)
                               / max(len(host_ms), 1) for k in parts},
        "queue_wait_s": _q([w for _, _, w, _ in admits]),
        "admit_s": _q([d for _, _, _, d in admits]),
        "migration_stall_ms": [1e3 * e.dur for e in evs
                               if e.name == "mig.apply"
                               and e.stats["arg"] > 0],
        "applied_at_s": [e.start - t_a for e in evs
                         if e.name == "mig.apply" and e.stats["arg"] > 0],
        "intervals_s": _q([e.dur for _, e in ivs], (50, 90, 100)),
        "longest_intervals": [
            {"s": iv.dur, "at_s": iv.start - t_a,
             "parts": _sum_by_name(ps.inside(evs, i)),
             "pauses": [(e.name, e.dur, e.stats["arg"])
                        for e in ps.inside(evs, i)
                        if e.name.startswith("host.")]}
            for i, iv in ivs[:3]],
    }
    for kind in ("host.gc", "host.compile"):
        p = [e for e in evs if e.name == kind]
        out[kind] = {"n": len(p), "s": sum(e.dur for e in p),
                     "max_s": max((e.dur for e in p), default=0.0),
                     "args": sorted({str(e.stats["arg"]) for e in p})[:8]}
    # t_submit = t_admit - queue wait, for the arrivals admitted in the window
    late = [t - w - arrival[rid] for rid, t, w, _ in admits if rid in arrival]
    out["lateness_s"] = _q(late, (50, 99, 100))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spans", choices=("on", "follow"), default="on")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    box: dict = {}

    def hook(eng):
        rec = box["rec"] = getattr(eng, "spans", None)
        if rec is not None and args.spans == "on":
            rec.enable()

    tr_dir = tempfile.mkdtemp(prefix="span_report_") if args.trace else None
    try:
        run = R.run_cell(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), trace_dir=tr_dir,
                         window_hook=hook, t_process=T_PROCESS,
                         log=lambda *a, **k: print(*a, file=sys.stderr))
    except R.NoAccelerator as e:
        print(f"[span_report] {e}", file=sys.stderr)
        return 3
    out = {"workload": args.workload, "seed": args.seed,
           "spans": args.spans, "trace": args.trace,
           "correct": run.result["correct"],
           "metrics": {k: v["value"]
                       for k, v in run.result["metrics"].items()},
           "device": run.result["device"],
           "counters": {k: run.counters[k] for k in
                        ("decode_steps", "slot_busy_steps", "applied_plans",
                         "queue_at_close", "finished_in_window")}}
    if run.result.get("breakdown"):
        out["hook_gaps"] = run.result["breakdown"]["idle_gaps"]
    rec = box.get("rec")
    if rec is not None:
        out["ring"] = ring_summary(rec.ring, run.window, run.arrival)
    if tr_dir:
        from bench import trace_reduce as TRR
        path = TRR.latest_xplane(tr_dir)
        prof = TRR.load(path)
        out["program_gaps"] = [list(g) for g in ps.named_gaps(prof)]
        idle: dict = {}
        for name, sec in ps.named_gaps(prof, k=1 << 30):
            idle[name] = idle.get(name, 0.0) + sec
        out["idle_s_by_span"] = dict(sorted(idle.items(), key=lambda x: -x[1]))
        scopes = ps.scope_seconds(path)
        busy = sum(scopes.values()) or 1.0
        out["scopes"] = sorted(([k, v, 100.0 * v / busy]
                                for k, v in scopes.items()),
                               key=lambda x: -x[1])[:16]
        out["top_ops"] = [[n, sc, v, 100.0 * v / busy]
                          for n, sc, v in ps.top_ops_by_scope(path)]
        shutil.rmtree(tr_dir, ignore_errors=True)
    line = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
