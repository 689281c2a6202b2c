"""The comparison that decides ``correct``.

After the window, a sample of the requests the window finished, drawn
from the seed and holding the one with the most served tokens and one of
every slot that finished a request, is run once through the plain
float32 reference (``bench/reference``), each prompt followed by the
tokens the engine served for it.  For every served
token the reference gives the gap by which that token's logit lies below
the reference's best at that position (0 when the engine picked the
reference's argmax).  Two numbers come of it: the widest gap over the
sample (``logit_gap``) and the mean gap per served token
(``mean_logit_gap``); the configuration file names the ones its cells
compare, with their limits, and PERF.md the readings they were set from.

A reference control (``quant="int8"`` or ``"fp8"``, run by
``bench/control.py`` and never by the benchmark's own runs) computes the
same reference on int8 (W8A8) or fp8 (e4m3) operands, a step below the
bfloat16 served, and reads, at the same positions, the gap of the token
that forward puts first: the reference put in the program's place.

Weights are regenerated from the seed one layer at a time, so this runs
after the engine and its state have been freed.  Each layer is run with
its kind of attention (``bench/work.py``'s ``layer_kinds``), one compiled
step per kind and stream.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def sample(finished, seed: int, min_tokens: int) -> list:
    """``finished``: [(prompt, served, slot)] of the window's finished
    requests.  The one with the most served tokens, then one request of
    every other slot that finished one, then more, in an order drawn from
    the seed, until ``min_tokens`` served tokens are in the sample.
    Returns [(prompt, served)]."""
    if not finished:
        return []
    order = [int(i) for i in np.random.default_rng(
        np.random.SeedSequence([int(seed), 7])).permutation(len(finished))]
    longest = max(range(len(finished)), key=lambda i: len(finished[i][1]))
    picked = [longest]
    slots = {finished[longest][2]}
    for i in order:
        if finished[i][2] not in slots:
            picked.append(i)
            slots.add(finished[i][2])
    n = sum(len(finished[i][1]) for i in picked)
    for i in order:
        if n >= min_tokens:
            break
        if i not in picked:
            picked.append(i)
            n += len(finished[i][1])
    return [finished[i][:2] for i in picked]


def _reference_module(name: str):
    path = HERE / "reference" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gaps(conf: dict, seed: int, seqs: list, *, quant=None,
         pad_to: int = 256) -> dict:
    """Reference gaps of ``seqs`` = [(prompt, served)].

    Returns {"gap": the widest gap of a served token, "mean_gap": the mean
    over served tokens, "per_request": [widest gaps], "tokens": served
    tokens compared} and, with ``quant``, the two numbers for the int8 or
    fp8 forward's first choices as "control_gap" and "control_mean_gap"."""
    import jax
    import jax.numpy as jnp

    from bench import weights as W
    from bench.work import layer_kinds

    ref = _reference_module(conf["reference"])
    spec = conf["model"]
    root = W.root_key(seed)
    # each sequence is padded to a multiple of ``pad_to`` (causal: the
    # padding never reaches an earlier position), so a few shapes compile
    toks = []
    for p, s in seqs:
        full = np.concatenate([np.asarray(p), np.asarray(s)]).astype(np.int32)
        T = -(-len(full) // pad_to) * pad_to
        toks.append(np.pad(full, (0, T - len(full))))
    streams = [None] + ([quant] if quant else [])

    with jax.default_matmul_precision("highest"):
        layer_w = jax.jit(lambda r, l: W.layer_weights(W.layer_key(r, l),
                                                       spec))
        outer = jax.jit(lambda r: W.outer_weights(W.outer_key(r), spec))(root)
        kinds = layer_kinds(spec)
        step = {(q, kd): jax.jit(lambda p, x, q=q, kd=kd: ref.layer(
                    spec, p, x, quant=q, kind=kd))
                for q in streams for kd in set(kinds)}
        xs = {q: [ref.embed(outer, jnp.asarray(t)) for t in toks]
              for q in streams}
        for l, kd in enumerate(kinds):
            p = layer_w(root, l)
            for q in streams:
                xs[q] = [step[q, kd](p, x) for x in xs[q]]
            del p

        @jax.jit
        def read(outer, x, xq, tok, lo, hi):
            """Widest and summed gap of the served tokens, and of the
            control's first choices, at positions lo..hi-1."""
            lg = ref.logits(spec, outer, x)
            best = jnp.max(lg[:-1], -1)
            served = jnp.take_along_axis(lg[:-1], tok[1:, None], -1)[:, 0]
            pos = jnp.arange(tok.shape[0] - 1)
            live = (pos >= lo) & (pos < hi)
            g = jnp.where(live, best - served, 0.0)
            if xq is None:
                return jnp.max(g), jnp.sum(g), jnp.zeros(()), jnp.zeros(())
            pick = jnp.argmax(ref.logits(spec, outer, xq, quant=quant)[:-1],
                              -1)
            gq = jnp.where(live, best - jnp.take_along_axis(
                lg[:-1], pick[:, None], -1)[:, 0], 0.0)
            return jnp.max(g), jnp.sum(g), jnp.max(gq), jnp.sum(gq)

        rows = []
        for i, (p, s) in enumerate(seqs):
            lo, hi = len(p) - 1, len(p) + len(s) - 1
            xq = xs[quant][i] if quant else None
            rows.append([float(v) for v in read(
                outer, xs[None][i], xq, jnp.asarray(toks[i]), lo, hi)])
    n = sum(len(s) for _, s in seqs)
    res = {"gap": max(r[0] for r in rows),
           "mean_gap": sum(r[1] for r in rows) / n,
           "per_request": [r[0] for r in rows], "tokens": n}
    if quant:
        res["control_gap"] = max(r[2] for r in rows)
        res["control_mean_gap"] = sum(r[3] for r in rows) / n
    return res


def judge(limits: dict, res: dict, quant=None):
    """The decision: every number the configuration names against its
    limit, read from the control's first choices where ``quant`` names a
    reference control.  Returns (correct, {name: {"value", "limit"}})."""
    pre = "control_" if quant else ""
    got = {"logit_gap": res[pre + "gap"],
           "mean_logit_gap": res[pre + "mean_gap"]}
    checks = {k: {"value": got[k], "limit": lim} for k, lim in limits.items()}
    return all(got[k] <= lim for k, lim in limits.items()), checks
