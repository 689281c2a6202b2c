"""Spans of the serving loop on the program's clock and the profiler's.

One ``SpanRecorder`` per engine; the controller shares it.  A span is a
``with rec.span(name):`` block at a layer boundary of the served path
(scheduler, paged KV, model step, controller, migration).  The recorder
is single-threaded: spans nest as a stack, and each record names its
parent by index.

Off by default, and off is free: a disabled ``span()`` returns one
shared no-op context manager -- no allocation, no clock read, no device
sync.  The recorder is on while ``enable()`` holds it on, or while a
``jax.profiler`` trace runs (checked once per scheduler step, at the
root span), so a profile of a live server carries the engine's spans
without a restart.  When on, every span goes to two places:

- ``ring``: ``Span`` records in a bounded ``deque``, times from
  ``time.monotonic()`` (the clock of ``Request.t_submit``);
- ``jax.profiler.TraceAnnotation``: the same name on the profiler's host
  plane, beside the device ops and on their clock, with ``rid`` and
  ``arg`` as event stats.

While on, two host pauses that hold the loop are recorded as well:
``host.gc`` (``gc.callbacks``: a collection, ``arg`` = generation) and
``host.compile`` (JAX's ``backend_compile_duration`` event: start = end -
duration, ``arg`` = the compiled function's name).

Span names and what ``arg`` carries:

=====================  =========================================
``sched.step``         one ``ServingEngine.step``; arg = decode step
``sched.admit``        pop to first token; rid; arg = queue wait (s)
``model.prefill_chunk``  one chunk dispatch; rid; arg = chunk start
``sched.first_token``  admission sample + readback; rid
``kv.mount``           one page-table mount; arg = slot row
``model.decode_dispatch``  upload + decode program call
``model.decode_wait``  ``block_until_ready`` on the logits
``sched.sample``       sampler + readback
``sched.emit``         per-slot emit / finish / retire loop
``ctl.interval``       ``_interval_plan``
``ctl.observe``        background load, monitor, load signal
``ctl.assign``         Algorithm 1 (or the bottleneck policy)
``ctl.payback``        the §III.G payback filter
``ctl.perms``          placement -> per-layer permutations, pairs
``ctl.estimate``       migration and pipelined-inference delay
``mig.apply``          ``_apply_plan``; arg = head moves planned
``mig.permute``        one decode state's weight/cache permute
``mig.head_rows``      kernel gather maps rebuilt
=====================  =========================================
"""
from __future__ import annotations

import collections
import gc
import time
import weakref
from typing import Deque, List, NamedTuple, Optional, Union

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_clock = time.monotonic
_profiling = jax.profiler.TraceAnnotation.is_enabled


class Span(NamedTuple):
    index: int                  # sequence number over the recorder's life
    name: str
    t0: float                   # time.monotonic() at open
    t1: float                   # ... and at close
    parent: int                 # index of the enclosing span, -1 at a root
    rid: int                    # request id, -1 where none
    arg: Union[int, float, str]  # per-name, see the module docstring


class _Off:
    """The shared no-op span: ``with`` binds None."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


OFF = _Off()


class _Open:
    """A live span: ``t0`` is read on entry, ``t1`` on exit, so callers
    that need the boundary's time read it here instead of a second
    clock."""
    __slots__ = ("rec", "name", "rid", "arg", "t0", "t1", "index",
                 "parent", "_ann")

    def __init__(self, rec, name, rid, arg, t0):
        self.rec, self.name, self.rid, self.arg = rec, name, rid, arg
        self.t0 = t0
        self.t1 = 0.0

    def __enter__(self):
        rec = self.rec
        stack = rec._stack
        self.parent = stack[-1] if stack else -1
        self.index = rec._next
        rec._next += 1
        stack.append(self.index)
        meta = {}
        if self.rid >= 0:
            meta["rid"] = self.rid
        if self.arg != -1:
            meta["arg"] = self.arg
        self._ann = jax.profiler.TraceAnnotation(self.name, **meta)
        self._ann.__enter__()
        if self.t0 is None:
            self.t0 = _clock()
        return self

    def __exit__(self, *exc):
        self.t1 = _clock()
        self._ann.__exit__(*exc)
        rec = self.rec
        rec._stack.pop()
        rec.ring.append(Span(self.index, self.name, self.t0, self.t1,
                             self.parent, self.rid, self.arg))
        return False


def _weakly(method):
    ref = weakref.WeakMethod(method)

    def call(*a, **kw):
        m = ref()
        if m is not None:
            m(*a, **kw)
    return call


class SpanRecorder:
    """Bounded span ring + profiler annotations; see the module
    docstring.  ``maxlen`` bounds the ring (oldest records drop)."""

    def __init__(self, maxlen: int = 1 << 16):
        self.ring: Deque[Span] = collections.deque(maxlen=maxlen)
        self.enabled = False          # held on by enable()
        self.active = False           # what span() reads
        self._stack: List[int] = []
        self._next = 0
        self._gc_open: Optional[tuple] = None
        # the hooks hold the recorder weakly: an engine dropped while a
        # trace runs leaves a no-op behind, not a live recorder
        self._hooks = (_weakly(self._on_gc), _weakly(self._on_compile))

    # ------------------------------------------------------------ switch
    def enable(self, on: bool = True):
        self.enabled = bool(on)
        self._set_active(self.enabled)

    def disable(self):
        self.enable(False)

    def _set_active(self, on: bool):
        if on == self.active:
            return
        self.active = on
        on_gc, on_compile = self._hooks
        if on:
            gc.callbacks.append(on_gc)
            jax.monitoring.register_event_duration_secs_listener(on_compile)
        else:
            gc.callbacks.remove(on_gc)
            jax.monitoring.unregister_event_duration_listener(on_compile)
            self._gc_open = None

    # ------------------------------------------------------------- spans
    def root(self, name: str, arg: Union[int, float, str] = -1):
        """A root span (one scheduler step): first re-reads whether the
        recorder is on, so a profiler trace started between steps is
        followed from the next step."""
        on = self.enabled or _profiling()
        if on != self.active:
            self._set_active(on)
        if not on:
            return OFF
        return _Open(self, name, -1, arg, None)

    def span(self, name: str, rid: int = -1,
             arg: Union[int, float, str] = -1,
             t0: Optional[float] = None):
        """A span under the open one.  ``t0``: a clock reading the caller
        already took at this boundary (it becomes the span's start)."""
        if not self.active:
            return OFF
        return _Open(self, name, rid, arg, t0)

    # ------------------------------------------------------------ pauses
    def _record(self, name, t0, t1, arg):
        parent = self._stack[-1] if self._stack else -1
        self.ring.append(Span(self._next, name, t0, t1, parent, -1, arg))
        self._next += 1

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            ann = jax.profiler.TraceAnnotation(
                "host.gc", arg=info.get("generation", -1))
            ann.__enter__()
            self._gc_open = (_clock(), info.get("generation", -1), ann)
        elif self._gc_open is not None:
            t0, gen, ann = self._gc_open
            self._gc_open = None
            t1 = _clock()
            ann.__exit__(None, None, None)
            self._record("host.gc", t0, t1, gen)

    def _on_compile(self, event: str, duration: float, **kw):
        if event == COMPILE_EVENT:
            t1 = _clock()
            self._record("host.compile", t1 - duration, t1,
                         str(kw.get("fun_name", "?")))

    # -------------------------------------------------------------- read
    def spans(self, name: Optional[str] = None) -> List[Span]:
        return [s for s in self.ring if name is None or s.name == name]
