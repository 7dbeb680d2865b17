"""Device-path spans and compile counters, on the profiler's clock.

The rest of :mod:`repro.obs` records the simulated fleet; this module
records the real device path: the Prompt Bank's Eqn-1 calls, the tuning
loop, and the host work between them (uploads, dispatches, syncs,
traces and compiles)::

    with jax.profiler.trace("/tmp/prof"):
        service.submit(req)              # or tuner.tune(...)
    device.snapshot()                    # counts, seconds, jit.* per span

Tracing is on exactly while a JAX profiler session is on (while
``jax.profiler.TraceAnnotation.is_enabled()``). Off, :func:`span` hands
back one shared no-op context: no clock read and nothing recorded. On,
each span enters a ``TraceAnnotation`` of the same name, so it lands in
the profiler's host plane on the device planes' clock, and is timed with
``perf_counter``: count, total and self seconds (duration minus what its
child spans cover), per span name and parent name. A root span gets a
fresh ``trace_id``; its descendants carry it as an annotation argument,
so the spans of one request or job share it.

While tracing is on, one ``jax.monitoring`` listener adds JAX's trace,
lowering and backend-compile seconds (a persistent-cache load reports as
a compile) and the persistent cache's hits and misses into ``jit.*``
series labelled by the innermost open span and its root. Nested events
(a jit traced inside another's trace) are counted once.

Each gradient program compiled on the save ladder of
:mod:`repro.train.remat` is counted under its rung (1 keeps the most
outputs for the backward pass), with the bytes of its temporaries from
the compile's ``memory_analysis()``: the outputs it keeps are part of
them.
"""
from __future__ import annotations

import bisect
import itertools
import threading
import time
from typing import Dict, List

import jax

from repro.obs.metrics import MetricsRegistry

_enabled = jax.profiler.TraceAnnotation.is_enabled

# JAX monitoring event -> jit.* series (seconds, counted once)
JIT_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_s",
    "/jax/core/compile/backend_compile_duration": "jit.compile_s",
}
JIT_COUNTS = {
    "/jax/compilation_cache/cache_hits": "jit.cache_hits",
    "/jax/compilation_cache/cache_misses": "jit.cache_misses",
}


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("tracer", "name", "ids", "parent", "root", "children",
                 "annotation", "t0")

    def __init__(self, tracer: "DeviceTracer", name: str, ids: Dict):
        self.tracer, self.name, self.ids = tracer, name, ids

    def __enter__(self):
        stack = self.tracer._stack()
        self.parent = stack[-1] if stack else None
        if self.parent is None:
            self.root = self
            self.ids["trace_id"] = next(self.tracer._ids)
        else:
            self.root = self.parent.root
            self.ids["trace_id"] = self.root.ids["trace_id"]
        self.children = 0.0
        self.annotation = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self.annotation.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.tracer._stack().pop()
        self.annotation.__exit__(*exc)
        if self.parent is not None:
            self.parent.children += dt
        self.tracer._record(self, dt)
        return False


class DeviceTracer:
    """Spans and ``jit.*`` counters of the device path, in a
    :class:`MetricsRegistry`:

    * ``span_s{span, parent}`` — histogram of each span's seconds
      (count and total);
    * ``span_self_s{span, parent}`` — counter of self seconds;
    * ``jit.trace_s``, ``jit.lower_s``, ``jit.compile_s`` ``{span, root}``
      — histograms of JAX's trace, lowering and compile-or-load seconds;
    * ``jit.cache_hits``, ``jit.cache_misses`` ``{span, root}`` —
      persistent-cache counters;
    * ``remat.temp_bytes{rung, span, root}`` — histogram of the
      temporaries' bytes of each gradient program compiled on that rung
      of the save ladder (its count is the number of programs).

    A parent or root that does not exist is labelled ``""``."""

    def __init__(self) -> None:
        self.registry = MetricsRegistry()
        self._series: Dict[tuple, tuple] = {}   # (span, parent) -> series
        self._local = threading.local()
        self._ids = itertools.count(1)
        # (start, end) of the outermost jit events so far, in order
        self._jit_marks: List[tuple] = []
        self._listening = False

    def span(self, name: str, **ids):
        """Context of one span; the shared no-op context while no
        profiler session is on."""
        if not _enabled():
            return NO_SPAN
        if not self._listening:
            self._listen()
        return _Span(self, name, ids)

    def grad_program(self, rung: int, temp_bytes: int) -> None:
        """Count one gradient program compiled on ``rung`` of the save
        ladder, with its temporaries' bytes, under the innermost open
        span; nothing while no profiler session is on."""
        if _enabled():
            self.registry.histogram("remat.temp_bytes", rung=rung,
                                    **self._where()).observe(temp_bytes)

    def snapshot(self) -> Dict[str, List[Dict]]:
        """``{"spans": [{span, parent, count, total_s, self_s}],
        "jit": [{metric, span, root, count, seconds}],
        "remat": [{rung, span, root, count, temp_bytes}]}`` since the
        last :meth:`reset` (``seconds`` is 0 for the cache counters;
        ``temp_bytes`` sums over the ``count`` programs)."""
        reg = self.registry
        self_s = {(lb["span"], lb["parent"]): c.value
                  for lb, c in reg.instruments("span_self_s")}
        spans = [dict(span=lb["span"], parent=lb["parent"], count=h.count,
                      total_s=h.sum, self_s=self_s[lb["span"], lb["parent"]])
                 for lb, h in reg.instruments("span_s")]
        jit = []
        for metric in (*JIT_SECONDS.values(), *JIT_COUNTS.values()):
            for lb, inst in reg.instruments(metric):
                count, seconds = ((inst.count, inst.sum)
                                  if metric in JIT_SECONDS.values()
                                  else (inst.value, 0.0))
                jit.append(dict(metric=metric, span=lb["span"],
                                root=lb["root"], count=count,
                                seconds=seconds))
        remat = [dict(rung=int(lb["rung"]), span=lb["span"], root=lb["root"],
                      count=h.count, temp_bytes=h.sum)
                 for lb, h in reg.instruments("remat.temp_bytes")]
        return {"spans": spans, "jit": jit, "remat": remat}

    def reset(self) -> None:
        """Forget every recorded span and counter."""
        self.registry = MetricsRegistry()
        self._series = {}
        self._jit_marks = []

    # -- internals ---------------------------------------------------------

    def _stack(self) -> List[_Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, s: _Span, dt: float) -> None:
        key = (s.name, s.parent.name if s.parent is not None else "")
        series = self._series.get(key)
        if series is None:
            labels = dict(span=key[0], parent=key[1])
            series = self._series[key] = (
                self.registry.histogram("span_s", **labels),
                self.registry.counter("span_self_s", **labels))
        series[0].observe(dt)
        series[1].inc(max(dt - s.children, 0.0))

    def _where(self) -> Dict[str, str]:
        stack = self._stack()
        return ({"span": stack[-1].name, "root": stack[0].name} if stack
                else {"span": "", "root": ""})

    def _on_time_span(self, event: str, start: float, end: float,
                      **kw) -> None:
        metric = JIT_SECONDS.get(event)
        if metric is None or not _enabled():
            return
        # events nest (a jit traced inside another's trace ends first):
        # take out what earlier events inside this one already counted
        i = bisect.bisect_left(self._jit_marks, (start,))
        own = (end - start) - sum(b - a for a, b in self._jit_marks[i:])
        self._jit_marks[i:] = [(start, end)]
        self.registry.histogram(metric, **self._where()).observe(
            max(own, 0.0))

    def _on_event(self, event: str, **kw) -> None:
        metric = JIT_COUNTS.get(event)
        if metric is not None and _enabled():
            self.registry.counter(metric, **self._where()).inc()

    def _listen(self) -> None:
        jax.monitoring.register_event_time_span_listener(self._on_time_span)
        jax.monitoring.register_event_listener(self._on_event)
        self._listening = True


TRACER = DeviceTracer()
span = TRACER.span
grad_program = TRACER.grad_program
snapshot = TRACER.snapshot
reset = TRACER.reset


def total(rows: List[Dict], key: str, **match) -> float:
    """Sum of ``key`` over the snapshot rows whose fields equal
    ``match``."""
    return sum(r[key] for r in rows
               if all(r[k] == v for k, v in match.items()))


def jit_seconds(snap: Dict[str, List[Dict]], **match) -> float:
    """Trace, lowering and compile-or-load seconds of the snapshot's
    ``jit`` rows that equal ``match`` (such as ``root="tune.job"``)."""
    return sum(total(snap["jit"], "seconds", metric=m, **match)
               for m in JIT_SECONDS.values())
