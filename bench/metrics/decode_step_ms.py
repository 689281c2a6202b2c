"""Model step (``TransformerLM.decode_step``): device time per execution
of the decode program, from the trace's module events."""
import numpy as np

from bench import trace_reduce as tr

MODULE = r"^jit_decode_step\b"      # jax.jit(model.decode_step)


def read(run):
    if not run.trace:
        return None
    d = tr.module_seconds(run.trace["prof"], MODULE)
    return 1e3 * float(np.mean(d)) if d else None
