"""Named host-clock spans at the boundaries of the solver's layers.

Every layer of a solve — ``prepare``/``build``, ``update``, the entry
labels and global relabel, the sweep loop, cut extraction and the
certificate — opens a span named ``maxflow.<layer>``::

    with spans.span("maxflow.build", n=n) as sp:
        meta, state, layout = build(...)
        sp.wait(state)            # close on the device arrays, when traced
        sp.set(K=meta.num_regions)

Recording is off by default.  Then a span is one flag test and a bare
``yield``: no record, no profiler annotation, no device sync.  While
recording (``start()``/``stop()``, or the ``recording()`` context):

* each span keeps a :class:`Span` record: name, start and end on
  ``time.perf_counter_ns`` (the clock callers time requests with), its own
  id, the id of the enclosing span and of the outermost (root) one, its
  attributes, and the backend-compile seconds and count that fell inside
  it;
* each span is also a ``jax.profiler.TraceAnnotation(name, **attrs)``, so
  inside a profiler session it lands in the trace on the device events'
  clock;
* ``sp.wait(outputs)`` blocks on those device outputs when the span
  closes, so a traced span holds its own device work instead of handing it
  to whatever syncs next;
* every ``/jax/core/compile/backend_compile_duration`` event (a compile,
  or a persistent-cache load where that cache is open) is charged to the
  innermost open span of the thread that compiled.

``drain()`` hands the records over and forgets them; ``summary()`` totals
them per name.  Open spans are kept per thread; records from every thread
go to one list.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_recording = False
_records: list["Span"] = []
_ids = itertools.count(1)
_open = threading.local()           # .stack: the thread's open spans
_listening = False


@dataclass
class Span:
    """One closed (or, inside its ``with``, open) span."""

    name: str
    id: int
    parent: int | None              # enclosing span's id; None for a root
    root: int                       # outermost enclosing span's id
    attrs: dict
    start_ns: int
    end_ns: int = 0
    compile_s: float = 0.0          # backend compiles charged to this span
    compiles: int = 0
    _outputs: list = field(default_factory=list, repr=False)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def wait(self, *outputs) -> None:
        """Block on ``outputs`` (device arrays or pytrees) when the span
        closes."""
        self._outputs.extend(outputs)

    def set(self, **attrs) -> None:
        """Attributes known only inside the span (they reach the record,
        not the profiler annotation, which opened with the span)."""
        self.attrs.update(attrs)


class _Off:
    """What a span yields while recording is off: accepts and drops."""

    def wait(self, *outputs) -> None:
        pass

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _on_compile(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT or not _recording:
        return
    stack = _stack()
    if stack:
        stack[-1].compile_s += duration
        stack[-1].compiles += 1


@contextlib.contextmanager
def span(name: str, **attrs):
    """A named span around one layer of the solver (see the module
    docstring); yields a handle with ``wait`` and ``set``."""
    if not _recording:
        yield _OFF
        return
    stack = _stack()
    sid = next(_ids)
    parent = stack[-1] if stack else None
    rec = Span(name=name, id=sid, parent=parent.id if parent else None,
               root=parent.root if parent else sid, attrs=dict(attrs),
               start_ns=time.perf_counter_ns())
    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield rec
            if rec._outputs:
                jax.block_until_ready(rec._outputs)
    finally:
        rec._outputs = []
        rec.end_ns = time.perf_counter_ns()
        stack.pop()
        _records.append(rec)


def start() -> None:
    """Turn recording on (registering the compile listener the first
    time)."""
    global _recording, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _listening = True
    _recording = True


def stop() -> list[Span]:
    """Turn recording off; returns (and forgets) the records."""
    global _recording
    _recording = False
    return drain()


def drain() -> list[Span]:
    """The records so far, in closing order; forgets them."""
    out = _records[:]
    del _records[:len(out)]
    return out


@contextlib.contextmanager
def recording():
    """Record inside the ``with``; the yielded list holds the records once
    it closes."""
    out: list[Span] = []
    start()
    try:
        yield out
    finally:
        out.extend(stop())


def summary(records) -> dict:
    """Per span name: ``count``, ``total_ms``, ``compile_ms`` and
    ``compiles``, names in order of first closing."""
    out: dict = {}
    for r in records:
        s = out.setdefault(r.name, dict(count=0, total_ms=0.0,
                                        compile_ms=0.0, compiles=0))
        s["count"] += 1
        s["total_ms"] += r.seconds * 1e3
        s["compile_ms"] += r.compile_s * 1e3
        s["compiles"] += r.compiles
    return out
