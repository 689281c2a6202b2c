"""Jaxpr audit of the serving hot path (JXP0xx findings).

Abstractly traces the engine's jitted hot functions — ``decode_step``,
``prefill_bucketed``, ``insert_slot`` and the resident-kernel dispatch —
with ``jax.make_jaxpr`` (no compile, no execution) and walks every eqn,
recursing into scan/while/cond/pjit/pallas sub-jaxprs:

JXP001  implicit dtype promotion on a cache-sized array: a
        ``convert_element_type`` that WIDENS an operand of at least
        ``big_elems`` elements.  A widened KV cache is the exact memory
        Algorithm 1 budgets — a stray f32 upcast of a bf16/int8 cache
        doubles (quadruples) the per-device resident bytes.
JXP002  host callback / transfer primitive inside the jitted body
        (``pure_callback``/``io_callback``/``debug_callback``/ ...): a
        hidden host sync per decode step that no bench row attributes.
JXP003  large closure-captured constant: a concrete array baked into the
        jaxpr consts.  Bakes weights into the executable (doubling their
        footprint) and retraces whenever the enclosing closure is
        rebuilt — the recompile-ladder seed.

``audit_hot_functions()`` builds the shared tiny audit model
(``hlo_audit.build_audit_setup``) and runs all hot functions through
``audit_jaxpr``.
"""
from __future__ import annotations

from typing import Any, Iterable, List, Tuple

import jax
import numpy as np

from repro.analysis import Finding

HOST_CALLBACK_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "outside_call", "host_callback_call", "infeed", "outfeed",
}
# benign converts: iota/bool masks and scalar bookkeeping promote freely
DEFAULT_BIG_ELEMS = 8192


def _sub_jaxprs(params: dict) -> Iterable[Any]:
    """Every Jaxpr/ClosedJaxpr reachable from an eqn's params (scan/while
    bodies, cond branches, pjit/pallas_call callees, custom_* rules)."""
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                yield item.jaxpr          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                # raw Jaxpr


def _iter_eqns(jaxpr) -> Iterable[Tuple[Any, Any]]:
    """(eqn, owning jaxpr) pairs, depth-first over sub-jaxprs."""
    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn, j
            stack.extend(_sub_jaxprs(eqn.params))


def _aval(var):
    return getattr(var, "aval", None)


def audit_jaxpr(closed_jaxpr, name: str, *,
                big_elems: int = DEFAULT_BIG_ELEMS) -> List[Finding]:
    """Walk one ClosedJaxpr for the three hazard classes."""
    findings: List[Finding] = []
    jaxpr = closed_jaxpr.jaxpr

    # JXP003: top-level consts are the closure captures (sub-jaxpr consts
    # are threaded as constvars and surface here too)
    for const in closed_jaxpr.consts:
        arr = np.asarray(const) if hasattr(const, "shape") else None
        if arr is not None and arr.size >= big_elems:
            findings.append(Finding(
                "JXP003", f"{name}/consts",
                f"closure-captured constant {arr.dtype}{list(arr.shape)} "
                f"({arr.size} elems) baked into the jaxpr — doubles its "
                f"footprint in the executable and forces a retrace when "
                f"the closure is rebuilt; pass it as an argument"))

    for eqn, _ in _iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim == "convert_element_type":
            inv = _aval(eqn.invars[0])
            outv = _aval(eqn.outvars[0])
            if inv is None or outv is None:
                continue
            size = int(np.prod(inv.shape)) if inv.shape else 1
            if size < big_elems:
                continue
            try:
                widen = (np.dtype(outv.dtype).itemsize
                         > np.dtype(inv.dtype).itemsize)
            except TypeError:
                widen = False
            if widen:
                findings.append(Finding(
                    "JXP001", f"{name}/{prim}",
                    f"implicit promotion {inv.dtype}{list(inv.shape)} -> "
                    f"{outv.dtype} on a cache-sized array ({size} elems): "
                    f"a widened resident buffer is exactly the memory the "
                    f"placement algorithm budgets — cast the small "
                    f"operand down instead"))
        elif prim in HOST_CALLBACK_PRIMITIVES:
            findings.append(Finding(
                "JXP002", f"{name}/{prim}",
                f"host callback `{prim}` inside the jitted hot function — "
                f"a host round-trip per decode step that no bench row "
                f"attributes; move it outside jit or behind a debug flag"))
    return findings


def audit_hot_functions(*, big_elems: int = None) -> List[Finding]:
    """Trace the four serving hot functions on the shared audit model."""
    from repro.analysis.hlo_audit import build_audit_setup
    from repro.kernels.decode_attention import decode_attention_resident

    setup = build_audit_setup()
    m, params, state, toks = (setup["model"], setup["params"],
                              setup["state"], setup["tokens"])
    cache_k = state["cache"]["k"]
    # "cache-sized" for THIS model: one full layer of KV rows
    big = big_elems or max(int(np.prod(cache_k.shape[1:])) // 2, 1024)

    findings: List[Finding] = []
    findings += audit_jaxpr(
        jax.make_jaxpr(m.decode_step)(params, state, toks),
        "decode_step", big_elems=big)
    findings += audit_jaxpr(
        jax.make_jaxpr(m.prefill_bucketed)(
            params, setup["bucket_state"], setup["bucket_tokens"],
            setup["bucket_lengths"]),
        "prefill_bucketed", big_elems=big)
    findings += audit_jaxpr(
        jax.make_jaxpr(m.insert_slot)(state, setup["sub_state"],
                                      np.int32(0)),
        "insert_slot", big_elems=big)
    # resident-kernel dispatch: identity gather map over all heads
    B, T = cache_k.shape[1], cache_k.shape[2]
    KvE, dh = cache_k.shape[3], cache_k.shape[4]
    H = setup["cfg"].n_heads
    q = jax.ShapeDtypeStruct((B, H, dh), cache_k.dtype)
    kv = jax.ShapeDtypeStruct((B, KvE, T, dh), cache_k.dtype)
    lengths = jax.ShapeDtypeStruct((B,), np.int32)
    rows = jax.ShapeDtypeStruct((H,), np.int32)
    findings += audit_jaxpr(
        jax.make_jaxpr(
            lambda q, k, v, ln, r: decode_attention_resident(
                q, k, v, ln, r, interpret=True))(q, kv, kv, lengths, rows),
        "decode_attention_resident", big_elems=big)
    return findings
