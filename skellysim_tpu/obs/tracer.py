"""Span tracer: one JSONL event stream for runtime telemetry.

skelly-scope's first leg (docs/observability.md). The reference instruments
its hot path with spdlog scope markers and one wall-clock timer around each
GMRES solve (`solver_hydro.cpp:81-91`); this module replaces that with a
structured event stream every surface shares: `System.run` / `_run_loop`,
the ensemble scheduler, and `bench.py` all emit through the SAME tracer, so
`python -m skellysim_tpu.obs summarize` renders run metrics, ensemble lane
churn, and bench group timings from one format.

Design constraints:

* **Import-light.** This module imports jax only lazily
  (`jax.block_until_ready`, and only when a span actually registered a
  device sync tree). Reaching it through the package still runs
  `skellysim_tpu/__init__.py`'s module-level `import jax` — which is why
  `bench.py`'s parent process (which must never import jax: a parent that
  has touched jax holds the chip its children need) pins its own `TELEMETRY_VERSION`
  literal instead of importing this module; only the bench *children*
  (which import jax anyway) construct tracers.
* **Zero-cost when inactive.** The module-level `span()` / `emit()` helpers
  consult the active tracer once and no-op without one, so the run loop and
  scheduler carry their instrumentation unconditionally.
* **Device-work attribution.** XLA dispatch is async: a jit call returns
  before the device finishes, so a naive span around it undercounts by
  >100x (the `_run_loop` wall_s lesson). A span that should absorb its
  device work registers the output pytree via ``sp.sync(tree)``; the span
  blocks on it at exit, so the duration covers the device execution.

Event lines are JSON objects with common keys ``ev`` (event kind), ``ts``
(monotonic seconds, arbitrary origin — deltas only), ``pid``, ``host``.
Kinds emitted here: ``telemetry`` (stream header, carries ``version``),
``span`` (``name``, ``path`` = slash-joined open-span stack, ``dur_s``,
plus caller fields), and whatever callers pass to `emit` (``compile`` from
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

#: version stamp of the event schema AND the bench artifact format
#: (bench.py pins its own copy — it cannot import this module in the
#: jax-free parent process; tests/test_obs.py asserts the two agree)
TELEMETRY_VERSION = 1


def provenance(downscaled=None) -> dict:
    """The self-description stamp timelines and bench artifacts share:
    ``jax_version`` + ``device_kind`` (+ ``downscaled`` when the caller
    states it) — skelly-pulse's answer to "which hardware/runtime
    produced these numbers?" (bench artifacts used to hand-stamp
    ``telemetry_version`` only).

    jax-free-safe: consults ``sys.modules`` instead of importing — a
    process that never imported jax (bench's parent) gets ``None``
    placeholders rather than a backend init, and the tracer header stays
    zero-cost in jax-free contexts. In a process whose backend is live
    (every CLI/run/bench child), ``jax.devices()`` is already cached.
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
    if downscaled is not None:
        info["downscaled"] = bool(downscaled)
    return info


class _Span:
    """Mutable handle yielded by `Tracer.span`: attach fields / a sync tree."""

    __slots__ = ("fields", "_sync")

    def __init__(self):
        self.fields = {}
        self._sync = None

    def note(self, **fields):
        """Attach extra fields to the span event emitted at exit."""
        self.fields.update(fields)

    def sync(self, tree):
        """Register a pytree to `jax.block_until_ready` at span exit, so the
        device work producing it is attributed to THIS span (returns the
        tree unchanged, for inline use)."""
        self._sync = tree
        return tree


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
        self._stack: list[str] = []
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

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """Nestable timed scope; emits ONE ``span`` event at exit whose
        ``path`` is the slash-joined stack of open spans (attribution) and
        whose ``dur_s`` includes any registered device sync."""
        sp = _Span()
        self._stack.append(name)
        t0 = time.perf_counter()
        try:
            yield sp
        finally:
            try:
                if sp._sync is not None:
                    import jax

                    jax.block_until_ready(sp._sync)
            finally:
                dur = time.perf_counter() - t0
                path = "/".join(self._stack)
                self._stack.pop()
                self.emit("span", name=name, path=path,
                          dur_s=round(dur, 6), **{**fields, **sp.fields})

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


_NULL_SPAN = _Span()


@contextlib.contextmanager
def _null_span():
    # a fresh-enough dummy: note()/sync() write into a shared throwaway
    _NULL_SPAN.fields.clear()
    _NULL_SPAN._sync = None
    yield _NULL_SPAN


def span(name: str, **fields):
    """`Tracer.span` on the active tracer, or an inert span when telemetry
    is off — instrumentation sites never branch."""
    tr = _ACTIVE
    if tr is None:
        return _null_span()
    return tr.span(name, **fields)


def emit(ev: str, **fields):
    """`Tracer.emit` on the active tracer; no-op when telemetry is off."""
    tr = _ACTIVE
    if tr is not None:
        tr.emit(ev, **fields)
