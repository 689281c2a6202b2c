"""The program's own spans and named scopes in a JAX profiler trace.

The engine records its spans (``repro.runtime.spans``: ``sched.step``,
``model.decode_wait``, ``ctl.assign``, ``mig.apply``, ...) as
``TraceAnnotation``s while a profiler trace runs, so they sit on the
host plane beside the benchmark's own annotations, with ``rid`` and
``arg`` as event stats.  A program without the recorder leaves none, and
everything here then returns an empty result.

The reductions (``host_step_seconds``, ``admissions``,
``controller_split``) take ``Ev`` lists, so the same code reads the
trace's host plane and the recorder's in-memory ring.

The model's ``jax.named_scope``s (``norm``, ``attention``, ``kv_write``,
``mlp``, ``lm_head``) reach the device plane as the ``tf_op`` stat of
each op's event metadata (``jit(decode_step)/while/body/closed_call/
attention/kv_write/reshape``), which ``jax.profiler.ProfileData`` does
not expose: ``scope_seconds`` parses the ``.xplane.pb`` with the
compiled ``xplane.proto`` that ships inside the tensorflow distribution,
loaded from its file so that tensorflow itself is not imported.

All times are seconds on the trace's clock (the ring's: the program's).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict, Iterator, List, NamedTuple, Tuple

from bench import trace_reduce as tr

PREFIXES = ("sched.", "model.", "kv.", "ctl.", "mig.", "host.")
SCOPES = ("kv_write", "attention", "mlp", "norm", "lm_head")
# the parts of a step that are not the host's own work: the device wait,
# the controller, the migration and admission (prefill)
NOT_HOST = ("model.decode_wait", "ctl.interval", "mig.apply", "sched.admit")


class Ev(NamedTuple):
    name: str
    start: float
    end: float
    stats: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


def base_name(name: str) -> str:
    """An annotation's name without metadata encoded after ``#``."""
    return name.split("#", 1)[0]


def ordered(evs) -> List[Ev]:
    """Sorted by start, an outer span before the spans it holds."""
    return sorted(evs, key=lambda e: (e.start, -e.end))


def spans(prof, name: str = None) -> List[Ev]:
    """The program's spans on the trace's host plane, ``ordered``."""
    out = []
    for p in prof.planes:
        if p.name != tr.HOST_PLANE:
            continue
        for line in p.lines:
            for e in line.events:
                n = base_name(e.name)
                if n.startswith(PREFIXES) and (name is None or n == name):
                    out.append(Ev(n, e.start_ns * 1e-9, e.end_ns * 1e-9,
                                  dict(e.stats)))
    return ordered(out)


def inside(evs: List[Ev], i: int) -> Iterator[Ev]:
    """The spans within ``evs[i]`` (``evs`` ``ordered``)."""
    outer = evs[i]
    for e in evs[i + 1:]:
        if e.start >= outer.end:
            return
        if e.end <= outer.end:
            yield e


# ----------------------------------------------------------- reductions
def host_step_seconds(evs: List[Ev]) -> List[float]:
    """Per decode step (a ``sched.step`` holding a ``model.decode_wait``):
    the step's time less the device wait, the controller interval, the
    migration and admissions inside it -- the host's own work (mounts,
    upload, dispatch, sample readback, emit)."""
    out = []
    for i, st in enumerate(evs):
        if st.name != "sched.step":
            continue
        inner = list(inside(evs, i))
        if any(e.name == "model.decode_wait" for e in inner):
            out.append(st.dur - sum(e.dur for e in inner
                                    if e.name in NOT_HOST))
    return out


def admissions(evs: List[Ev]) -> List[Tuple[int, float, float, float]]:
    """(rid, t_admit, queue wait, pop to first token) per ``sched.admit``;
    its ``arg`` is ``t_admit - t_submit``."""
    return [(e.stats.get("rid", -1), e.start, e.stats.get("arg", 0.0), e.dur)
            for e in evs if e.name == "sched.admit"]


def controller_split(evs: List[Ev]) -> Dict[str, float]:
    """Seconds of the ``ctl.interval`` spans and of each span name inside
    them (``ctl.assign``, ``ctl.payback``, ...)."""
    out: Dict[str, float] = {}
    for i, iv in enumerate(evs):
        if iv.name != "ctl.interval":
            continue
        out["ctl.interval"] = out.get("ctl.interval", 0.0) + iv.dur
        for e in inside(evs, i):
            out[e.name] = out.get(e.name, 0.0) + e.dur
    return out


def named_gaps(prof, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps of the first TPU plane, each named by
    the innermost program span over its middle ("host" where none)."""
    return tr.idle_gaps(prof, [e[:3] for e in spans(prof)], k=k)


# ------------------------------------------------------ device scopes
def _xspace_class():
    tf = importlib.util.find_spec("tensorflow")
    if tf is None or not tf.origin:
        raise ImportError("no compiled xplane.proto: tensorflow is not "
                          "installed")
    path = Path(tf.origin).parent / "tsl/profiler/protobuf/xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.XSpace


def device_ops(xplane_path: str, device: str = "/device:TPU:0"
               ) -> List[Tuple[str, str, float, float]]:
    """(op name, ``tf_op``, start, end) of every event on ``device``'s
    ``XLA Ops`` line."""
    space = _xspace_class().FromString(Path(xplane_path).read_bytes())
    for plane in space.planes:
        if plane.name != device:
            continue
        stat_names = {k: m.name for k, m in plane.stat_metadata.items()}

        def tf_op(meta) -> str:
            for st in meta.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    if st.WhichOneof("value") == "ref_value":
                        return stat_names.get(st.ref_value, "")
                    return st.str_value
            return ""
        ops = {k: (m.name, tf_op(m)) for k, m in plane.event_metadata.items()}
        out = []
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            base_ps = line.timestamp_ns * 1000
            for ev in line.events:
                s = (base_ps + ev.offset_ps) * 1e-12
                name, op = ops.get(ev.metadata_id, ("", ""))
                out.append((name, op, s, s + ev.duration_ps * 1e-12))
        return out
    return []


def scope_of(tf_op: str) -> str:
    """``jit(decode_step)/while/body/attention/kv_write/reshape:`` ->
    ``decode_step:kv_write``: the program and the innermost scope."""
    if not tf_op:
        return "(no op_name)"
    parts = tf_op.rstrip(":").split("/")
    prog = parts[0][4:-1] if parts[0].startswith("jit(") else parts[0]
    inner = [p for p in parts[1:] if p in SCOPES]
    return f"{prog}:{inner[-1] if inner else '-'}"


def scope_seconds(xplane_path: str, device: str = "/device:TPU:0"
                  ) -> Dict[str, float]:
    """Device self seconds per program and named scope (an op nested in
    a loop's event counts once)."""
    return tr.self_seconds([(scope_of(op), s, e) for _, op, s, e
                            in device_ops(xplane_path, device)])


def top_ops_by_scope(xplane_path: str, k: int = 12,
                     device: str = "/device:TPU:0"
                     ) -> List[Tuple[str, str, float]]:
    """The ``k`` ops with the most device self seconds, each with the
    scope that issued it."""
    sec = tr.self_seconds([(f"{tr.short_name(n)}\t{scope_of(op)}", s, e)
                           for n, op, s, e in device_ops(xplane_path, device)])
    top = sorted(sec.items(), key=lambda x: -x[1])[:k]
    return [(*key.split("\t"), v) for key, v in top]
