"""On-chip benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``)
and a traffic mix (``bench/traffic/``).  The run

1. fails (exit 3, no result) unless JAX finds a TPU and as many chips as
   the cell asks for;
2. makes the weights on the device from ``--seed`` (``bench/weights.py``)
   and builds the engine through ``repro.serving.engine.make_engine``
   (paged KV, the Pallas decode kernel, the interval controller live);
3. sets up: JAX's compilation cache in ``<checkout>/.jax_cache``, the
   slots filled with requests already in flight, and steps run until the
   controller has applied a plan (of heads, or of a routed model's
   experts), so that every program the window runs (prefill chunk, decode
   step, page mount, sampler, the migration's permutes) has been compiled
   or loaded;
4. offers the mix's arrivals open-loop on the wall clock for ``--seconds``
   and drives ``ServingEngine.step``; every token is timestamped through
   the engine's ``token_sink``;
5. with ``--trace 1`` traces a few seconds of that window
   (``jax.profiler``), with the engine's ``step``, ``_admit``,
   ``_interval_plan`` and ``_apply_plan`` wrapped in
   ``TraceAnnotation``s, and reports the per-layer metrics; with
   ``--trace 0`` it reports the end-to-end metrics;
6. frees the engine and compares a sample of the finished requests with
   the plain float32 reference (``bench/check.py``).

A control run (``bench/control.py``, never the benchmark's own runs)
puts a step of lower precision in the program's place and is judged by
the same comparison, so that ``correct`` comes out false: ``kv_int8``
switches on the program's own int8 KV cache (the configuration's
``kv_quant``); ``int8`` and ``fp8`` put the plain reference, on int8
(W8A8) or fp8 (e4m3) operands, in the program's place.

The last line of standard output is one JSON object; the numbers the
comparison read, each beside its limit, are the last lines of standard
error and the last key of that object.

End-to-end metrics are read by ``bench/e2e/<name>.py`` and per-layer
metrics by ``bench/metrics/<name>.py``: each exposes ``read(run)`` and
returns None where it finds nothing to read.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
HOOKED = ("step", "_admit", "_interval_plan", "_apply_plan")
SETUP_MAX_INTERVALS = 12          # set-up gives the controller this many
                                  # intervals to apply its first plan
CONTROLS = ("kv_int8", "int8", "fp8")


class NoAccelerator(RuntimeError):
    pass


# ------------------------------------------------------------------ files
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str):
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfile = {c["name"]: c["file"] for c in bench["configs"]}[cell["config"]]
    conf = load_json(root / cfile)
    traffic = load_json(root / "bench" / "traffic"
                        / f"{cell['traffic']}.json")
    return bench, cell, conf, traffic


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_names(bench: dict, cell: dict, kind: str) -> List[str]:
    """The metrics of ``kind`` ("end_to_end" | "per_layer") the cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [m["name"] for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


# ------------------------------------------------------------------ model
REQUIRED = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
            "vocab_size", "norm", "norm_eps", "mlp", "qkv_bias", "rope_theta",
            "rope_fraction", "dtype")
RENAMED = {"norm": "norm_type", "mlp": "mlp_type"}   # spec key -> field
ABSENT = {"n_experts": 0, "experts_per_token": 0, "sliding_window": 0}


def model_config(conf: dict):
    """The registry's config with the file's overrides.  Every key of the
    file's ``"model"`` block that is a field of the program's
    ``ModelConfig`` (``norm`` and ``mlp`` under their field names) must
    hold what the program will run; ``layer_types`` must be the program's
    kinds of attention; a key the reference alone reads is named in its
    ``SPEC_KEYS``; any other key stops the run.  An absent key of
    ``ABSENT`` means its value there."""
    from repro.configs import get_config

    from bench import check as C
    cfg = get_config(conf["arch"]).with_overrides(**conf["overrides"])
    spec = conf["model"]
    fields = {f.name for f in dataclasses.fields(cfg)}
    ref_only = set(C._reference_module(conf["reference"]).SPEC_KEYS)
    missing = [k for k in REQUIRED if k not in spec]
    unknown = [k for k in spec if RENAMED.get(k, k) not in fields
               and k != "layer_types" and k not in ref_only]
    if missing or unknown:
        raise SystemExit(f"{conf['arch']}: the model block lacks {missing} "
                         f"and states {unknown}, which neither the program "
                         f"nor the reference reads")
    want = dict(ABSENT, **spec)
    got = {k: getattr(cfg, RENAMED.get(k, k)) for k in want
           if RENAMED.get(k, k) in fields}
    got = {k: list(v) if isinstance(v, tuple) else v for k, v in got.items()}
    if "layer_types" in want and "layer_types" not in fields:
        # the program runs one window on every layer where it has one
        got["layer_types"] = ["window" if cfg.sliding_window else "full"] \
            * cfg.n_layers
    bad = {k: (want[k], got[k]) for k in got if want[k] != got[k]}
    if bad or cfg.param_dtype != spec["dtype"] or cfg.tie_embeddings:
        raise SystemExit(f"{conf['arch']}: file and program disagree: {bad}")
    return cfg


def build_engine(cfg, conf: dict, params_fn, **kw):
    """``make_engine`` as a server calls it, with the benchmark's weights
    in place of the model's own initializer."""
    import repro.serving.engine as E
    orig = E.build_model

    def build(*a, **k):
        model = orig(*a, **k)
        model.init = lambda key: params_fn()
        return model

    e = conf["engine"]
    E.build_model = build
    try:
        return E.make_engine(cfg, mode="continuous", paged=True,
                             page_size=e["page_size"], use_kernel=True,
                             n_slots=e["n_slots"], max_seq=e["max_seq"],
                             lam=e["lam"], seed=e["seed"], **kw)
    finally:
        E.build_model = orig


# -------------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    cell: dict
    conf: dict
    traffic: dict
    seconds: float
    window: tuple = (0.0, 0.0)              # host clock, open .. close
    setup_s: float = 0.0
    arrival: Dict[int, float] = dataclasses.field(default_factory=dict)
    times: Dict[int, List[float]] = dataclasses.field(default_factory=dict)
    slot: Dict[int, int] = dataclasses.field(default_factory=dict)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Optional[Dict[str, Any]] = None
    peaks: Optional[dict] = None
    gaps: Optional[dict] = None              # what the comparison read
    result: Optional[dict] = None            # the last line's object

    @property
    def spec(self) -> dict:
        return self.conf["model"]

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]


def _listen_compiles(box: list):
    from jax import monitoring

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box.append(kw.get("fun_name", "?"))
    monitoring.register_event_duration_secs_listener(on)


def _hook(eng, trace_on: list, found: list):
    """Wrap the engine's scheduler methods in TraceAnnotations (on the
    instance: the program is not edited)."""
    import functools

    import jax
    for name in HOOKED:
        fn = getattr(eng, name, None)
        if fn is None:
            continue
        found.append(name)

        def wrapped(*a, _fn=fn, _n=f"ServingEngine.{name}", **k):
            if not trace_on:
                return _fn(*a, **k)
            with jax.profiler.TraceAnnotation(_n):
                return _fn(*a, **k)
        setattr(eng, name, functools.wraps(fn)(wrapped))


def run_cell(root: Path, name: str, seed: int, seconds: float,
             trace: bool, *, require_accelerator: bool = True,
             t_process: float = T_PROCESS, traffic_override=None,
             trace_dir: Optional[str] = None, check: bool = True,
             control: Optional[str] = None, window_hook=None,
             log=print) -> Run:
    """One run of cell ``name`` from checkout ``root``; returns the Run,
    whose ``result`` is the object of the last line.  ``control`` is one of
    ``CONTROLS`` (see the module's docstring).  ``window_hook(engine)``
    runs as the window opens (the fault tests break the timed path
    there)."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control {control!r} is not one of {CONTROLS}")
    bench, cell, conf, traffic = load_cell(root, name)
    if traffic_override:
        traffic = dict(traffic, **traffic_override)
    for p in (root / "src", root):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        raise SystemExit(f"the program is not in this checkout: {e}")
    cache_dir = use_compile_cache()
    import jax
    import numpy as np
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import check as C
    from bench import traffic as TR
    from bench import weights as W

    devices = jax.devices()
    dev = devices[0]
    if require_accelerator and (dev.platform != "tpu"
                                or len(devices) < cell["chips"]):
        raise NoAccelerator(f"cell {name} needs {cell['chips']} TPU chip(s); "
                            f"JAX found {len(devices)} {dev.platform}")
    peaks = None
    if dev.platform == "tpu":
        table = load_json(HERE / "peaks.json")["devices"]
        if dev.device_kind not in table:
            raise SystemExit(f"no peaks for device kind {dev.device_kind!r}")
        peaks = table[dev.device_kind]
    used = devices[:cell["chips"]]

    cfg = model_config(conf)
    if control == "kv_int8":
        cfg = cfg.with_overrides(kv_quant=True)
    spec = conf["model"]
    e = conf["engine"]
    run = Run(cell=cell, conf=conf, traffic=traffic, seconds=float(seconds),
              peaks=peaks)
    log(f"[bench] {name} seed={seed} device={dev.device_kind} x{len(devices)}"
        f" cache={cache_dir}", file=sys.stderr)

    root_key = W.root_key(seed)
    eng = build_engine(cfg, conf, lambda: W.make_params(root_key, spec))
    tok_t = run.times

    slot_of = run.slot

    def sink(req, tok, done):
        if not done:
            tok_t.setdefault(req.rid, []).append(time.monotonic())
            if len(req.out_tokens) == 1:
                slot_of[req.rid] = next(s for s, r in enumerate(eng.slots)
                                        if r is req)

    straggler = traffic.get("straggler")
    injected: list = []

    def on_token(req, tok, done):
        sink(req, tok, done)
        if straggler and not injected and eng.migration_log:
            eng.net.inject_straggler(straggler["device"],
                                     slowdown=straggler["slowdown"])
            injected.append(eng.decode_steps)

    eng.token_sink = on_token
    trace_on: list = []
    hooked: list = []
    if trace:
        _hook(eng, trace_on, hooked)

    # ---------------------------------------------------------- set-up
    n_fill = int(traffic.get("in_flight", 0))
    supply = TR.in_flight(dict(traffic, in_flight=n_fill
                               + SETUP_MAX_INTERVALS * e["n_slots"]),
                          seed, spec["vocab_size"])
    for a in supply[:n_fill]:
        eng.submit(a.prompt, a.max_new_tokens)
    supply = supply[n_fill:]
    while eng.queue:
        eng.step()
    while not any(m["applied"] or m["expert_applied"]
                  for m in eng.migration_log):
        if len(eng.migration_log) >= SETUP_MAX_INTERVALS:
            raise SystemExit(f"no plan applied in {SETUP_MAX_INTERVALS} "
                             f"controller intervals of set-up")
        if not eng._active():
            # the controller needs slots that decode; top them up
            for a in supply[:e["n_slots"]]:
                eng.submit(a.prompt, a.max_new_tokens)
            supply = supply[e["n_slots"]:]
        eng.step()
    jax.block_until_ready(eng.states)
    arrivals = TR.schedule(traffic, seconds, seed, spec["vocab_size"])
    compiles: list = []
    _listen_compiles(compiles)
    c0 = dict(decode_steps=eng.decode_steps, busy=eng.slot_busy_steps,
              n_log=len(eng.migration_log), n_fin=len(eng.finished))
    live: list = []
    pages_total = sum(a.n_pages for a in eng.allocators)
    orig_decode = eng._decode_jit

    def decode(*a):
        live.append(sum(al.live_pages for al in eng.allocators)
                    / pages_total)
        return orig_decode(*a)
    eng._decode_jit = decode
    queue_max = [0]
    if window_hook is not None:
        window_hook(eng)

    # ---------------------------------------------------------- window
    t0 = time.monotonic()
    run.setup_s = t0 - t_process
    t_end = t0 + seconds
    run.window = (t0, t_end)
    tr_at = t0 + traffic.get("trace_at_s", 2.0)
    tr_end = tr_at + traffic.get("trace_seconds", 4.0)
    tr_dir = None
    i = 0
    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        while i < len(arrivals) and t0 + arrivals[i].t <= now:
            rid = eng.submit(arrivals[i].prompt, arrivals[i].max_new_tokens)
            run.arrival[rid] = t0 + arrivals[i].t
            i += 1
        if trace and not trace_on and now >= tr_at and tr_dir is None:
            tr_dir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tr_dir, profiler_options=opts)
            trace_on.append(time.monotonic())
            n_tok0 = {r: len(v) for r, v in tok_t.items()}
        elif trace_on and len(trace_on) == 1 and now >= tr_end:
            jax.block_until_ready(eng.states)
            trace_on.append(time.monotonic())
            jax.profiler.stop_trace()
            n_tok1 = {r: len(v) for r, v in tok_t.items()}
        queue_max[0] = max(queue_max[0], len(eng.queue))
        if eng.queue or eng._active():
            eng.step()
        else:
            nxt = t0 + arrivals[i].t if i < len(arrivals) else t_end
            time.sleep(max(0.0, min(nxt, t_end) - time.monotonic()))
    jax.block_until_ready(eng.states)
    if trace_on and len(trace_on) == 1:
        trace_on.append(time.monotonic())
        jax.profiler.stop_trace()
        n_tok1 = {r: len(v) for r, v in tok_t.items()}
    t_close = time.monotonic()
    in_window_compiles = list(compiles)

    # ------------------------------------------------------- counters
    log_w = eng.migration_log[c0["n_log"]:]
    run.counters = dict(
        n_slots=eng.n_slots,
        decode_steps=eng.decode_steps - c0["decode_steps"],
        slot_busy_steps=eng.slot_busy_steps - c0["busy"],
        live_share=live,
        plan_s=[m["plan_s"] for m in log_w],
        applied_plans=sum(1 for m in log_w if m["applied"]),
        expert_plans=sum(1 for m in log_w if m["expert_applied"]),
        queue_at_close=len(eng.queue))
    mem = [d.memory_stats() or {} for d in used]
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    if trace_on:
        t_a, t_b = trace_on[0], trace_on[1]
        decode_ctx, prompts = [], []
        for rid in tok_t:
            a, b = n_tok0.get(rid, 0), n_tok1.get(rid, 0)
            if a == b:
                continue
            L0 = len(_req(eng, rid).prompt)
            for j in range(a, b):
                if j == 0:
                    prompts.append(L0)
                else:
                    decode_ctx.append(L0 + j)
        run.trace = dict(window_s=t_b - t_a, decode_ctx=decode_ctx,
                         prompts=prompts, chunk=eng.prefill_chunk,
                         n_devices=len(used))

    # ------------------------------------------------------- finished
    fin = [(np.asarray(r.prompt), list(r.out_tokens), r.max_new_tokens,
            run.slot.get(r.rid, -1))
           for r in eng.finished[c0["n_fin"]:]
           if tok_t.get(r.rid) and run.in_window(tok_t[r.rid][-1])]
    attempted = len(run.arrival)
    del eng, orig_decode, decode, on_token
    gc.collect()
    log(f"[bench] device bytes live after freeing the engine: "
        f"{sum(x.nbytes for x in jax.live_arrays())}", file=sys.stderr)

    # -------------------------------------------------------- metrics
    kind = "per_layer" if trace else "end_to_end"
    metrics: Dict[str, dict] = {}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace:
        from bench import trace_reduce as TRR
        prof = TRR.load(TRR.latest_xplane(tr_dir))
        run.trace["prof"] = prof
        device["busy_s"] = TRR.busy_seconds(prof, len(used))
        device["window_s"] = run.trace["window_s"]
        spans = TRR.host_spans(prof, [f"ServingEngine.{h}" for h in hooked])
        breakdown = {"device_ops": [list(x) for x in
                                    TRR.top_ops(prof, n_devices=len(used))],
                     "idle_gaps": [list(x) for x in
                                   TRR.idle_gaps(prof, spans)]}
    for m in metric_names(bench, cell, kind):
        sub = "metrics" if trace else "e2e"
        val = load_module(root / "bench" / sub / f"{m}.py").read(run)
        if val is not None:
            metrics[m] = {"value": float(val), "unit": units[m]}
    if run.trace is not None:
        run.trace.pop("prof", None)
        if trace_dir is None and tr_dir:
            shutil.rmtree(tr_dir, ignore_errors=True)

    # ----------------------------------------------------- comparison
    checks: Dict[str, dict] = {}
    correct, failed = True, 0
    if check:
        limits = conf["check"]["limits"]
        short = sum(1 for p, s, n, _ in fin
                    if len(s) != min(n, e["max_seq"] - 1 - len(p)))
        seqs = C.sample([(p, s, sl) for p, s, _, sl in fin], seed,
                        traffic.get("check_tokens", 300))
        if seqs:
            t_check = time.monotonic()
            quant = control if control in ("int8", "fp8") else None
            res = run.gaps = C.gaps(conf, seed, seqs, quant=quant)
            log(f"[bench] reference over {len(seqs)} requests took "
                f"{time.monotonic() - t_check!r} s; widest gap "
                f"{res['gap']!r}, mean gap {res['mean_gap']!r}",
                file=sys.stderr)
            correct, checks = C.judge(limits, res, quant)
            failed = 0 if correct else len(seqs)
        else:
            correct = False
        checks["tokens_compared"] = {"value": res["tokens"] if seqs else 0,
                                     "limit": 1}
        checks["short_requests"] = {"value": short, "limit": 0}
        correct = correct and short == 0
    log(f"[bench] setup_s={run.setup_s!r} window_s={t_close - t0!r} "
        f"arrivals={attempted} finished_in_window={len(fin)} "
        f"applied_plans={run.counters['applied_plans']} "
        f"expert_plans={run.counters['expert_plans']} "
        f"intervals={len(run.counters['plan_s'])} "
        f"compiles_in_window={len(in_window_compiles)} "
        f"{sorted(set(in_window_compiles))} hooked={hooked} "
        f"straggler_at_step={injected} decode_steps="
        f"{run.counters['decode_steps']} queue_max={queue_max[0]} "
        f"plan_s_sum={sum(run.counters['plan_s'])!r} plan_s_max="
        f"{max(run.counters['plan_s'], default=0)!r} control={control}",
        file=sys.stderr)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checks
    run.counters["finished_in_window"] = len(fin)
    run.result = out
    return run


def _req(eng, rid):
    for r in list(eng.finished) + [s for s in eng.slots if s is not None] \
            + list(eng.queue):
        if r.rid == rid:
            return r
    raise KeyError(rid)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), trace_dir=args.trace_dir).result
    except NoAccelerator as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    for k, v in out["check"].items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
