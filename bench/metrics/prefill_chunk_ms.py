"""Model step (``TransformerLM.prefill_paged``): device time per execution
of the chunked-prefill program (one 64-token chunk), from the trace."""
import numpy as np

from bench import trace_reduce as tr

MODULE = r"^jit_prefill_paged\b"    # jax.jit(model.prefill_paged)


def read(run):
    if not run.trace:
        return None
    d = tr.module_seconds(run.trace["prof"], MODULE)
    return 1e3 * float(np.mean(d)) if d else None
