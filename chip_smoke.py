"""Bring-up smoke on one TPU chip: serve musicgen-large at its published
widths through ``repro.launch.serve`` and check what came out.

    python chip_smoke.py

All 48 layers at d_model 2048, 32 heads of 64, bf16, random weights from
a fixed seed; paged KV (64-token pages) decoded by the Pallas kernel, 4
slots of 1024 tokens, the interval controller every 8 steps with a 20x
straggler on simulated device 0.  Eight requests with prompts of 32-512
tokens ask for 64 new tokens each.

Exits non-zero, without the final JSON line, when JAX finds no TPU or any
check fails.  Everything runs in this one process: it holds the chip.
The timings printed are smoke readings of one run, not benchmark numbers.
"""
from __future__ import annotations

import collections
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
REQUESTS, NEW_TOKENS = 8, 64
HBM_BYTES = 16 * 1024 ** 3                 # one v5e chip
SERVE_ARGV = ["--arch", "musicgen-large", "--paged", "--page-size", "64",
              "--use-kernel", "--slots", "4", "--max-seq", "1024",
              "--lam", "8", "--straggler", "0",
              "--requests", str(REQUESTS), "--tokens", str(NEW_TOKENS),
              "--mixed-lengths", "--min-prompt-len", "32",
              "--prompt-len", "512"]
# kernel vs jnp decode logits of one step on the same params and state:
# both paths carry bf16 activations through 48 layers and the kernel's
# online softmax sums in another order, so they agree to bf16 rounding
# amplified by depth, not bit for bit.  On the CPU (interpret mode, 48
# bf16 layers) they differ by about 0.05 at max|logit| near 4; the bound
# leaves room for the TPU's bf16-pass f32 matmuls on the jnp side, while a
# wrong head row or page moves logits by O(max|logit|)
LOGIT_ATOL, LOGIT_RTOL = 0.1, 0.05
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def _listen_compiles() -> dict:
    """fun_name -> seconds per compile (trace + lower + XLA compile or
    persistent-cache read), from JAX's own monitoring events."""
    from jax import monitoring
    spans: dict = collections.defaultdict(list)

    def on_event(event, duration, **kw):
        if event not in COMPILE_EVENTS:
            return
        name = kw.get("fun_name", "?")      # "f" when traced, "jit(f)" after
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        if event == COMPILE_EVENTS[0]:
            spans[name].append(0.0)
        if spans[name]:
            spans[name][-1] += duration

    monitoring.register_event_duration_secs_listener(on_event)
    return spans


def smoke(serve_argv) -> list:
    """Serve ``serve_argv`` and check the run; returns the failures."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models.api import build_model

    compiles = _listen_compiles()
    failures = []
    eng = serve.main(serve_argv)

    # every request finished with all of its tokens
    short = [(r.rid, len(r.out_tokens)) for r in eng.finished
             if len(r.out_tokens) != NEW_TOKENS]
    print(f"[smoke] finished={len(eng.finished)}/{REQUESTS} short={short}")
    if len(eng.finished) != REQUESTS or short:
        failures.append("not every request finished with its tokens")

    for name in ("prefill_paged", "decode_step"):
        secs = compiles.get(name) or [float("nan")]
        print(f"[smoke] first {name} compile_s={secs[0]!r} "
              f"(compiles={len(compiles.get(name, []))})")

    # the decode program dispatches the Pallas kernel
    rows = eng.rows_per_group
    toks = jnp.asarray(eng._next[:rows])
    text = eng._decode_jit.lower(eng.params, eng.states[0],
                                 toks).compile().as_text()
    has_kernel = "tpu_custom_call" in text
    print(f"[smoke] decode program has tpu_custom_call: {has_kernel}")
    if not has_kernel:
        failures.append("decode program has no Pallas kernel")

    steps = list(eng.monitor.slots[0].step_times)
    print(f"[smoke] steady decode step (smoke, not a benchmark): "
          f"median_ms={1e3 * statistics.median(steps)!r} over the last "
          f"{len(steps)} steps of {eng.decode_steps}")

    log = eng.migration_log
    plan_s = [m["plan_s"] for m in log]
    applied = [m for m in log if m["applied"]]
    infeasible = [m["step"] for m in log if m["infeasible"]]
    print(f"[smoke] controller intervals={len(log)} "
          f"host_s_median={statistics.median(plan_s)!r} "
          f"host_s_max={max(plan_s)!r} infeasible_at={infeasible}")
    print(f"[smoke] applied migrations={len(applied)} plans, "
          f"{sum(m['n_migrations'] for m in applied)} head moves")
    if infeasible:
        failures.append("a controller interval was infeasible")
    if not applied:
        failures.append("no head migration was applied")

    # kernel vs jnp: one decode step on the same params and state, after
    # the run's migrations, with a request live in slot 0
    rng = np.random.default_rng(1)
    eng.submit(rng.integers(0, eng.cfg.vocab_size, size=200),
               max_new_tokens=4)
    eng.step()
    state, toks = eng.states[0], jnp.asarray(eng._next[:rows])
    live = [s for s in range(rows) if eng.slots[s] is not None]

    def logits(model):
        # logits only: neither step may consume the shared state, and a
        # second full cache per step would crowd the chip's HBM
        step = jax.jit(lambda p, s, t: model.decode_step(p, s, t)[0])
        return np.asarray(step(eng.params, state, toks), np.float32)[live]

    got = logits(eng.model)
    ref = logits(build_model(eng.cfg, use_kernel=False))
    err = float(np.max(np.abs(got - ref)))
    bound = LOGIT_ATOL + LOGIT_RTOL * float(np.max(np.abs(ref)))
    same_argmax = bool(np.all(got.argmax(-1) == ref.argmax(-1)))
    print(f"[smoke] kernel vs jnp logits: max_abs_err={err!r} "
          f"bound={bound!r} (atol {LOGIT_ATOL} + rtol {LOGIT_RTOL} x "
          f"max|ref|) finite={bool(np.isfinite(got).all())} "
          f"same_argmax={same_argmax}")
    if not (np.isfinite(got).all() and err <= bound):
        failures.append("kernel and jnp logits disagree")

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[smoke] peak_bytes_in_use={peak!r} "
          f"bytes_limit={stats.get('bytes_limit')!r}")
    if peak is None or peak >= HBM_BYTES:
        failures.append("peak device memory unknown or not below 16 GiB")
    return failures


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"[smoke] device_kind={dev.device_kind} count={len(devices)} "
          f"compile_cache={cache_dir} entries_before={cached}")
    t0 = time.monotonic()
    failures = smoke(SERVE_ARGV)
    print(f"[smoke] wall_s={time.monotonic() - t0!r}")
    if failures:
        print(f"[smoke] FAILED: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
