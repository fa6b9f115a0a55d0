"""The run loop's step record: where the host's time of every step went.

`obs.tracer`'s spans measure themselves on every step of every run; this
module keeps what they measure when no tracer listens and no capture runs
(docs/observability.md "The step record"). `System.run` tells its ``run``
span to collect into a `StepRecorder`; `_run_loop` closes one record a trial
step, at the step's trailing ``clock_read``. The records TILE the time
inside ``run`` spans: each covers the interval from the close of the one
before it (or from the run's entry) to its own close, so the time a step
spent under no span at all is in its ``other`` and nothing is left out.

One record holds ``loop_s`` (the interval on `time.perf_counter`, the clock
of the tracer's ``span`` events) and its ``start``; ``host_ms``, the
milliseconds by leaf span (keys as the run-loop span table has them, plus
``other``; a span that did not run is an absent key, not a zero); and
``counters``, what the process's own counters moved over the interval
(`COUNTERS`). A record over `SLOW_RATIO` times the median of those before it
(and `SLOW_MIN_EXCESS_S` over it) is a slow step: it says so in its metrics
row (``slow``), as a ``fault`` event ``kind="slow_step"`` and as ONE WARNING
log line, all three from the one record.

jax-free, and nothing here touches the device.
"""

from __future__ import annotations

import gc
import logging
import os
import resource
import statistics
import time
from collections import deque

from . import tracer as obs_tracer

logger = logging.getLogger("skellysim_tpu")

#: records a `StepRecorder` keeps: four to nine windows of the benchmark's
#: cells (26-113 steps each), some 100 KB
RING_RECORDS = 256

#: a record is held against the median ``loop_s`` of up to this many records
#: before it: long enough that one stall among them does not move the
#: median, short enough to follow a scene whose steps grow (dynamic
#: instability adds fibers; two fibers nearing raise the iteration count)
SLOW_BASE_RECORDS = 64

#: ... once this many exist: a process's first steps compile and fill caches
#: (the benchmark's two warm calls read 30 s and 0.4 s), so a median over
#: fewer says nothing
SLOW_MIN_RECORDS = 8

#: ... and is slow over this many times that median. The stalls this is for
#: read 6x to 10x (4.16 s among 0.63-0.74 s, 4.0 s over 1.4 s: PERF.md
#: section 7); what must NOT flag reads well under 2x (the walkthrough's
#: alternation of 2- and 3-sweep steps 1.38x, the mesh cells' climb in
#: iterations across a window: `step_max_over_p50` in PERF.md section 5)
SLOW_RATIO = 2.0

#: ... and by at least this many seconds: a step of 2 ms among steps of 1 ms
#: is not worth a line, and what a flag itself costs (the log line, the
#: event: 0.1-0.3 ms, which land in the NEXT record) can then never flag
#: that one in turn
SLOW_MIN_EXCESS_S = 0.010

#: the counters of a record, as deltas over its interval, and what each
#: would point to (docs/observability.md): ``majflt`` / ``minflt`` page
#: faults, ``nivcsw`` involuntary context switches, ``inblock`` /
#: ``oublock`` block-device reads and writes (`resource.getrusage`, the whole
#: process); ``gc_passes`` collector passes by generation and ``gc_s`` the
#: seconds inside them (`gc.callbacks`); ``psi_*_us`` the microseconds some
#: task of the machine stalled on memory, the disk, a core
#: (``/proc/pressure/*``, ``some total``; None where it cannot be read)
COUNTERS = ("majflt", "minflt", "nivcsw", "inblock", "oublock", "gc_passes",
            "gc_s", "psi_mem_us", "psi_io_us", "psi_cpu_us")

_PSI_FILES = {"psi_mem_us": "/proc/pressure/memory",
              "psi_io_us": "/proc/pressure/io",
              "psi_cpu_us": "/proc/pressure/cpu"}


class _GcWatch:
    """Collector passes by generation and the seconds inside them."""

    def __init__(self):
        self.passes = [0, 0, 0]
        self.seconds = 0.0
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None
            self.passes[info["generation"]] += 1


#: process-wide by nature, so made once, at the first recorder: the one
#: `gc.callbacks` entry, and the pressure files held open (a `pread` costs a
#: third of open-read-close; None for a file that cannot be opened)
_GC_WATCH = None
_PSI_FDS = None


def _gc_watch() -> _GcWatch:
    global _GC_WATCH
    if _GC_WATCH is None:
        _GC_WATCH = _GcWatch()
        gc.callbacks.append(_GC_WATCH)
    return _GC_WATCH


def read_pressure() -> dict:
    """``some total`` of each pressure file in microseconds, None where the
    file cannot be read (a kernel without PSI, a sealed /proc): never a
    zero for "not seen"."""
    global _PSI_FDS
    if _PSI_FDS is None:
        _PSI_FDS = {}
        for key, path in _PSI_FILES.items():
            try:
                _PSI_FDS[key] = os.open(path, os.O_RDONLY)
            except OSError:
                _PSI_FDS[key] = None
    out = {}
    for key, fd in _PSI_FDS.items():
        out[key] = None
        if fd is not None:
            try:
                # "some avg10=0.00 avg60=0.00 avg300=0.00 total=12345\n..."
                line = os.pread(fd, 128, 0).split(b"\n", 1)[0]
                out[key] = int(line.rsplit(b"=", 1)[1])
            except (OSError, ValueError, IndexError):
                pass
    return out


def read_counters() -> dict:
    """The counters as they stand (`COUNTERS`), one read of each."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    watch = _gc_watch()
    now = {"majflt": ru.ru_majflt, "minflt": ru.ru_minflt,
           "nivcsw": ru.ru_nivcsw, "inblock": ru.ru_inblock,
           "oublock": ru.ru_oublock, "gc_passes": tuple(watch.passes),
           "gc_s": watch.seconds}
    now.update(read_pressure())
    return now


def _moved(before: dict, after: dict) -> dict:
    out = {}
    for key in COUNTERS:
        a, b = before[key], after[key]
        if a is None or b is None:
            out[key] = None
        elif key == "gc_passes":
            out[key] = [y - x for x, y in zip(a, b)]
        elif key == "gc_s":
            out[key] = round(b - a, 6)
        else:
            out[key] = b - a
    return out


def row_fields(record: dict) -> dict:
    """A record's part of the metrics row (`system.METRICS_FIELDS`):
    ``loop_s`` to 1e-6 s, ``host_ms`` to 1e-3 ms, ``slow`` (which carries
    the counters, so the row of a step that was not slow stays small)."""
    return {"loop_s": round(record["loop_s"], 6),
            "host_ms": {k: round(v, 3)
                        for k, v in record["host_ms"].items()},
            "slow": record["slow"]}


class StepRecorder:
    """The step records of one `System`, across its `run` calls: the last
    `RING_RECORDS` in ``ring``, ``count`` of them ever closed."""

    def __init__(self):
        self.ring: deque = deque(maxlen=RING_RECORDS)
        self.count = 0
        self._t0 = None         # the open interval's start; None outside a run
        self._counters0 = None
        self._ms: dict = {}     # the open interval's leaf spans
        self._carried_s = 0.0   # the last run's tail, see `leave`

    def span_closed(self, path: str, dur_s: float, leaf: bool):
        """The collecting ``run`` span's sink (`obs.tracer._Span.collect`):
        a leaf's time goes under its path below ``step``."""
        if leaf:
            key = path[5:] if path.startswith("step/") else path
            self._ms[key] = self._ms.get(key, 0.0) + dur_s * 1e3

    def enter(self):
        """At ``run``'s entry: the first record of this call opens here."""
        if self._t0 is not None:    # the last call raised out of its loop
            self._ms, self._carried_s = {}, 0.0
        self._t0 = time.perf_counter()
        self._counters0 = read_counters()

    def close(self, step: int) -> dict:
        """Close the open record at the end of trial ``step`` (the run
        call's own index, the metrics row's ``step``) and open the next: one
        clock read and one read of the counters do both."""
        now = time.perf_counter()
        counters = read_counters()
        loop_s = now - self._t0 + self._carried_s
        ms = self._ms
        ms["other"] = max(loop_s * 1e3 - sum(ms.values()), 0.0)
        record = {"n": self.count, "step": step, "start": self._t0,
                  "loop_s": loop_s, "host_ms": ms,
                  "counters": _moved(self._counters0, counters),
                  "slow": None}
        self._judge(record)
        self.ring.append(record)
        self.count += 1
        self._t0, self._counters0 = now, counters
        self._ms, self._carried_s = {}, 0.0
        return record

    def leave(self):
        """At ``run``'s exit. What follows a call's last record (the last
        row's write) is in no record of that call; it is carried into the
        first record of the next call on this recorder, so a caller that
        re-enters ``run(max_steps=1)`` every step loses none of the loop's
        time and the records still tile the time inside ``run`` spans."""
        self._carried_s += time.perf_counter() - self._t0
        self._t0 = None

    def _judge(self, record: dict):
        """Mark ``record`` slow, and say so, where it is (module docstring;
        the constants above)."""
        if len(self.ring) < SLOW_MIN_RECORDS:
            return
        base = list(self.ring)[-SLOW_BASE_RECORDS:]
        p50 = statistics.median(r["loop_s"] for r in base)
        loop_s = record["loop_s"]
        if loop_s <= max(SLOW_RATIO * p50, p50 + SLOW_MIN_EXCESS_S):
            return
        # the leaf that is furthest over what it usually takes
        excess = {}
        for leaf, ms in record["host_ms"].items():
            usual = [r["host_ms"][leaf] for r in base if leaf in r["host_ms"]]
            excess[leaf] = ms - (statistics.median(usual) if usual else 0.0)
        leaf = max(excess, key=excess.get)
        c = record["counters"]
        slow = {"over_p50": round(loop_s / p50, 3), "in": leaf,
                "excess_ms": round(excess[leaf], 3), "counters": c}
        record["slow"] = slow
        obs_tracer.emit("fault", kind="slow_step", n=record["n"],
                        step=record["step"], loop_s=round(loop_s, 6), **slow)
        logger.warning(
            "slow step n=%d loop=%.3fs (p50 %.3fs) in=%s +%.3fs majflt=%s "
            "nivcsw=%s gc_s=%s psi_mem_us=%s psi_io_us=%s", record["n"],
            loop_s, p50, leaf, excess[leaf] / 1e3, c["majflt"], c["nivcsw"],
            c["gc_s"], c["psi_mem_us"], c["psi_io_us"])
