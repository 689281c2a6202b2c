"""Kernel (``kernels/decode_attention.py``
``decode_attention_paged_resident``): the least time the chip needs for
the traced decode steps' attention, over the kernel's device time.

The least time is the larger of needed FLOPs over the bf16 peak and
needed bytes over HBM bandwidth (``bench/work.py``): K and V of the live
tokens once per KV head, plus q and the output, at the context each
traced decode token had.  Pages the kernel walks past a slot's length,
and K/V pages it reads again for each query head of a group, are not
needed work and are not counted."""
from bench import trace_reduce as tr
from bench import work

KERNEL = r"^%decode_attention_paged_resident\b"   # the pallas_call


def read(run):
    t = run.trace
    if not t or not run.peaks or not t["decode_ctx"]:
        return None
    secs = sum(tr.op_seconds(t["prof"], KERNEL))
    if secs <= 0:
        return None
    flops, byts = work.decode_attention(run.spec, t["decode_ctx"])
    need = max(flops / run.peaks["bf16_flops"],
               byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / secs
