"""Span tracer: one JSONL event stream for runtime telemetry.

skelly-scope's first leg (docs/observability.md). The reference instruments
its hot path with spdlog scope markers and one wall-clock timer around each
GMRES solve (`solver_hydro.cpp:81-91`); this module replaces that with a
structured event stream every surface shares: `System.run` / `_run_loop`,
the ensemble scheduler and the serve loop all emit through the SAME tracer,
so `python -m skellysim_tpu.obs summarize` renders run metrics and ensemble
lane churn from one format.

Design constraints:

* **Import-light.** This module imports jax only lazily (the first
  `span` asks `jax.profiler` for `TraceAnnotation`). Reaching it through
  the package still runs `skellysim_tpu/__init__.py`'s module-level
  `import jax`; importing jax initialises no backend.
* **Near-free when inactive.** `emit()` consults the active tracer once and
  no-ops without one. `span()` always enters a
  `jax.profiler.TraceAnnotation("skelly/<path>")` — a whole span costs
  about 3 us while no profiler capture runs (CPU reading; the inert span it
  replaces cost 1.1 us) — so the run loop and scheduler carry their
  instrumentation unconditionally, and a capture (`--profile DIR`) holds
  the host spans in the same dump, on the same clock, as the device ops:
  `obs.profile` labels every device idle gap with the span that covers it.
* **A span's time outlives it where someone collects.** `_Span.__exit__`
  measures every span whether or not a tracer listens; a span told to
  `collect` hands the `(path below it, seconds, leaf)` of every span that
  closes under it to a sink, tracer or no tracer, capture or no capture.
  `System.run` collects under its ``run`` span into the step record
  (`obs.step_record`): one list walk and one call a span.
* **A span never changes when the program waits.** XLA dispatch is async: a
  jit call returns before the device finishes, so a span around it times
  the enqueue (the run loop's ``dispatch``), and the span around the first
  host fetch of a result times the device (``wait``). Spans only observe
  that; none blocks at exit.

Event lines are JSON objects with common keys ``ev`` (event kind), ``ts``
(`time.perf_counter()` seconds: one monotonic clock per process, arbitrary
origin — compare within a stream only), ``pid``, ``host``. Kinds emitted
here: ``telemetry`` (stream header, carries ``version``), ``span``
(``name``, ``path`` = slash-joined open-span stack, ``start`` on the
``ts`` clock, ``dur_s``, ``parent`` = the enclosing span's path or null,
``step`` = the caller's or the nearest enclosing span's step id, plus
caller fields), and whatever callers pass to `emit` (``compile`` from
`obs.compile_log`, ``lane`` from the ensemble scheduler). The step records
of the run-loop/ensemble metrics JSONL (`system.METRICS_FIELDS`) carry no
``ev`` key; `obs summarize` accepts both shapes in any mix.
"""

from __future__ import annotations

import contextlib
import json
import os
import socket
import time
from typing import Optional

#: version stamp of the event schema
TELEMETRY_VERSION = 1


def provenance() -> dict:
    """The self-description stamp of a telemetry header: ``jax_version`` +
    ``device_kind`` — "which hardware/runtime produced these numbers?".

    jax-free-safe: consults ``sys.modules`` instead of importing — a
    process that never imported jax gets ``None`` placeholders rather than
    a backend init, and the tracer header stays zero-cost in jax-free
    contexts. In a process whose backend is live (every CLI run),
    ``jax.devices()`` is already cached.
    """
    import sys

    jax = sys.modules.get("jax")
    info = {"jax_version": getattr(jax, "__version__", None)
            if jax is not None else None}
    kind = None
    if jax is not None:
        try:
            devs = jax.devices()
            kind = devs[0].device_kind if devs else None
        except Exception:
            kind = None
    info["device_kind"] = kind
    return info


#: the open spans of this process, outermost first: [name, step, whether a
#: span has opened under it]. Module state like `_ACTIVE` below:
#: annotations need the path with no tracer on
_STACK: list = []

#: the open collecting spans, outermost first: (depth of `_STACK` below the
#: collector, sink)
_COLLECTORS: list = []

_TRACE_ANNOTATION = None


class _Span:
    """One timed scope (`span` / `Tracer.span`): a profiler annotation
    always, its time handed to every collecting span open around it, a
    ``span`` event at exit where a tracer listens."""

    __slots__ = ("name", "fields", "_tracer", "_ann", "_t0", "_collects")

    def __init__(self, name: str, fields: dict, tracer):
        self.name = name
        self.fields = fields
        self._tracer = tracer
        self._collects = False

    def note(self, **fields):
        """Attach extra fields to the span event emitted at exit."""
        self.fields.update(fields)

    def collect(self, sink):
        """From now until this (open) span closes, every span that closes
        under it calls ``sink(path, dur_s, leaf)``: ``path`` the slash-joined
        names below this span, ``leaf`` whether no span opened under the
        one that closed. No tracer or capture is needed."""
        _COLLECTORS.append((len(_STACK), sink))
        self._collects = True

    def __enter__(self):
        global _TRACE_ANNOTATION
        if _TRACE_ANNOTATION is None:
            from jax.profiler import TraceAnnotation as _TRACE_ANNOTATION
        step = None
        if _STACK:
            parent = _STACK[-1]
            step = parent[1]
            parent[2] = True
        step = self.fields.get("step", step)
        _STACK.append([self.name, step, False])
        path = "/".join(e[0] for e in _STACK)
        kw = self.fields if step is None else {**self.fields, "step": step}
        self._ann = _TRACE_ANNOTATION("skelly/" + path, **kw)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        names = [e[0] for e in _STACK]
        path = "/".join(names)
        _, step, parent_of_any = _STACK.pop()
        if self._collects:
            _COLLECTORS.pop()
        for depth, sink in _COLLECTORS:
            sink("/".join(names[depth:]), dur, not parent_of_any)
        if self._tracer is not None:
            self._tracer.emit(
                "span", name=self.name, path=path,
                start=round(self._t0, 6), dur_s=round(dur, 6),
                parent=path.rpartition("/")[0] or None,
                **{**self.fields, "step": step})
        return False


class Tracer:
    """Append telemetry events to a JSONL file (or an in-memory list).

    ``path=None`` keeps events in ``self.events`` — the test/analysis mode.
    File mode appends (a resumed run extends its stream; the header line
    re-stamps the segment) and flushes per event: a crashed run keeps every
    event up to the crash.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.events = [] if path is None else None
        self._fh = open(path, "a") if path else None
        self._pid = os.getpid()
        try:
            self._host = socket.gethostname()
        except Exception:
            self._host = "unknown"
        # header carries the provenance stamp: a telemetry stream is
        # self-describing about runtime + hardware (None placeholders in
        # jax-free processes — provenance() never imports jax itself)
        self.emit("telemetry", version=TELEMETRY_VERSION, **provenance())

    # ------------------------------------------------------------------ emit

    def emit(self, ev: str, **fields):
        rec = {"ev": ev, "ts": round(time.perf_counter(), 6),
               "pid": self._pid, "host": self._host}
        rec.update(fields)
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        else:
            self.events.append(rec)

    def span(self, name: str, **fields):
        """Nestable timed scope; emits ONE ``span`` event at exit whose
        ``path`` is the slash-joined stack of open spans (attribution)."""
        return _Span(name, fields, self)

    # ----------------------------------------------------------- lifecycle

    def close(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ------------------------------------------------------- active-tracer state

#: the process-wide active tracer; instrumented code paths (run loop,
#: scheduler, compile observer) consult it through `active()` so telemetry
#: is a no-op until someone installs one via `use()`
_ACTIVE: Optional[Tracer] = None


def active() -> Optional[Tracer]:
    return _ACTIVE


@contextlib.contextmanager
def use(tracer: Optional[Tracer]):
    """Install ``tracer`` as the process-wide active tracer for the block
    (``None`` is allowed and keeps telemetry off — callers need no branch)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    try:
        yield tracer
    finally:
        _ACTIVE = prev


def span(name: str, **fields):
    """A `_Span` reporting to the active tracer, if any — instrumentation
    sites never branch."""
    return _Span(name, fields, _ACTIVE)


def emit(ev: str, **fields):
    """`Tracer.emit` on the active tracer; no-op when telemetry is off."""
    tr = _ACTIVE
    if tr is not None:
        tr.emit(ev, **fields)
