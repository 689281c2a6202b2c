"""Record the small chip trace ``fixtures/decode_trace.xplane.pb``.

    python3 bench/tests/record_trace.py        # on a TPU

A two-layer musicgen-shaped engine (the published head width, 8 heads)
serves two short requests through chunked prefill and the Pallas decode
kernel, with ``step`` wrapped in a TraceAnnotation as ``bench/run.py``
does; a few steps are traced.  ``test_trace_reduce.py`` reads the file
and ``fixtures/decode_trace.json`` holds what was counted when it was
recorded.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parents[1])]


def main() -> int:
    import jax
    import numpy as np

    from bench import trace_reduce as tr
    from bench import weights as W
    from bench.run import build_engine, model_config
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    conf = json.loads((HERE.parent / "configs" / "musicgen-large.json")
                      .read_text())
    model = dict(conf["model"], n_layers=2, d_model=512, n_heads=8,
                 n_kv_heads=8, d_ff=1024)
    conf = dict(conf, model=model,
                overrides={k: model[k] for k in
                           ("n_layers", "d_model", "n_heads", "n_kv_heads",
                            "d_ff")},
                engine=dict(conf["engine"], n_slots=4, max_seq=256))
    eng = build_engine(model_config(conf), conf,
                       lambda: W.make_params(W.root_key(0), model))
    rng = np.random.default_rng(0)
    for n in (100, 40):
        eng.submit(rng.integers(0, 2048, size=n), max_new_tokens=12)
    for _ in range(3):
        eng.step()                                 # compile outside
    step = eng.step

    def traced():
        with jax.profiler.TraceAnnotation("ServingEngine.step"):
            return step()
    eng.step = traced
    eng.submit(rng.integers(0, 2048, size=70), max_new_tokens=4)
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(4):
        eng.step()
    jax.block_until_ready(eng.states)
    jax.profiler.stop_trace()
    src = tr.latest_xplane(d)
    dst = HERE / "fixtures" / "decode_trace.xplane.pb"
    shutil.copy(src, dst)
    prof = tr.load(str(dst))
    info = {"decode_steps": len(tr.module_seconds(prof, r"^jit_decode_step\b")),
            "prefill_chunks": len(tr.module_seconds(prof,
                                                    r"^jit_prefill_paged\b")),
            "kernel_calls": len(tr.op_seconds(
                prof, r"^%decode_attention_paged_resident\b")),
            "busy_s": tr.busy_seconds(prof)}
    (HERE / "fixtures" / "decode_trace.json").write_text(json.dumps(info))
    print(json.dumps(info), dst.stat().st_size)
    shutil.rmtree(d)
    return 0


if __name__ == "__main__":
    sys.exit(main())
