"""Device, whole step: model FLOPs of every prefill and decode token the
traced window processed (``bench/work.py``: 2 per weight a token
multiplies, the LM head for each row of logits, attention over each
token's context) over the traced seconds times the chip's bf16 peak."""
from bench import work


def read(run):
    t = run.trace
    if not t or not run.peaks or not t.get("window_s"):
        return None
    if not (t["decode_ctx"] or t["prompts"]):
        return None
    f = work.model_flops(run.spec, t["decode_ctx"], t["prompts"], t["chunk"])
    return 100.0 * f / (t["window_s"] * run.peaks["bf16_flops"]
                        * t["n_devices"])
