"""Reduction of a profiler trace (`.xplane.pb`) to what the metrics read.

Read with `jax.profiler.ProfileData`, nothing else. What a TPU v5e trace
holds (looked at by hand, PR 25): one plane per chip, ``/device:TPU:<i>``,
whose line ``XLA Ops`` has one event per executed HLO op with its start and
duration in nanoseconds — an op inside a while loop once per trip, and the
``while`` itself as one event that spans its trips — and a host plane
``/host:CPU`` whose thread lines hold the `TraceAnnotation` spans the
harness writes around its own calls. Device and host events share one
clock. An event's name is the op's whole HLO text; its stats are
``device_offset_ps``, ``device_duration_ps`` and a time scale — NO scope
path: `jax.named_scope` names (``prep / gmres / refine / advance``) do not
reach a TPU trace's events, so no metric of a step phase is read here.

* busy      = the union of the device-op intervals inside the window
* idle gaps = the complement, each labelled by the harness span that covers
              most of it
* op names  = the HLO text cut to ``%name opcode[:custom_call_target]``;
              containers (``while``, ``conditional``, ``call``) are left
              out of the ranking, since their trips are counted as ops
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field

#: the lines of a device plane that hold executed ops, in order of preference
OP_LINES = ("XLA Ops",)
#: ops that only contain other ops
CONTAINERS = ("while", "conditional", "call")
#: every span the harness writes starts with this
SPAN_PREFIX = "chipbench_"


@dataclass
class TraceSummary:
    window_ns: tuple[float, float]
    #: per device plane: list of (start_ns, end_ns, name)
    device_ops: dict = field(default_factory=dict)
    #: harness spans on the host: list of (start_ns, end_ns, name)
    spans: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    # ---- busy / idle ------------------------------------------------------
    def _clipped(self, ops):
        lo, hi = self.window_ns
        return sorted((max(s, lo), min(e, hi)) for s, e, *_ in ops
                      if e > lo and s < hi)

    def busy_intervals(self, plane: str):
        return merge(self._clipped(self.device_ops[plane]))

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the device planes."""
        if not self.device_ops:
            return 0.0
        per = [sum(e - s for s, e in self.busy_intervals(p))
               for p in self.device_ops]
        return sum(per) / len(per) * 1e-9

    def idle_gaps(self, top: int = 10):
        """The longest gaps of the first device plane, each with the
        harness span that overlaps it most: [[label, seconds], ...]."""
        if not self.device_ops:
            return []
        plane = sorted(self.device_ops)[0]
        lo, hi = self.window_ns
        gaps, cur = [], lo
        for s, e in self.busy_intervals(plane):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            out.append([self._label(s, e), (e - s) * 1e-9])
        return out

    def _label(self, s: float, e: float) -> str:
        # most of the gap covered wins; among equals the innermost
        # (shortest) span; the window span itself only where no other does
        cands = [(min(e, b) - max(s, a), -(b - a), name)
                 for a, b, name in self.spans
                 if min(e, b) > max(s, a) and name != SPAN_PREFIX + "window"]
        if not cands:
            return "outside every harness span"
        cands.sort(key=lambda c: (round(c[0] / (e - s), 2), c[1]))
        return cands[-1][2][len(SPAN_PREFIX):]

    # ---- by op -------------------------------------------------------------
    def top_ops(self, top: int = 10):
        """[[family, seconds], ...] summed over the window and averaged
        over the device planes. A family is `short_name` without the
        instruction's number, with the count of executed ops behind it
        (a step runs hundreds of thousands of small fusions: no single
        instruction holds a per cent of the time); containers left out."""
        lo, hi = self.window_ns
        total, count = {}, {}
        memo: dict = {}
        for ops in self.device_ops.values():
            for s, e, name in ops:
                d = min(e, hi) - max(s, lo)
                if d <= 0:
                    continue
                fam = memo.get(name)
                if fam is None:
                    fam = memo[name] = family(name)
                total[fam] = total.get(fam, 0.0) + d
                count[fam] = count.get(fam, 0) + 1
        n = max(len(self.device_ops), 1)
        rows = sorted(total.items(), key=lambda kv: -kv[1])
        rows = [r for r in rows if r[0].split(" ")[-1] not in CONTAINERS]
        return [[f"{k} x{count[k] // n}", v * 1e-9 / n] for k, v in rows[:top]]

    def span_seconds(self, name: str) -> list[float]:
        return [(b - a) * 1e-9 for a, b, n in self.spans if n == name]

    def busy_in_spans(self, span_name: str) -> list[float]:
        """Seconds in which an op ran on the first device plane inside each
        harness span of that name, one entry per span."""
        if not self.device_ops:
            return []
        ops = self.device_ops[sorted(self.device_ops)[0]]
        return [sum(e - s for s, e in merge(
                    sorted((s, e) for s, e, _ in ops if s >= a and e <= b)))
                * 1e-9
                for a, b, n in self.spans if n == span_name]


def merge(intervals):
    """The union of sorted (start, end) intervals, as [start, end] pairs."""
    merged = []
    for s, e in intervals:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def family(hlo: str) -> str:
    name, _, op = short_name(hlo).partition(" ")
    return re.sub(r"[.\d]+$", "", name) + " " + op


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load_profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    return ProfileData.from_file(path)


def short_name(hlo: str) -> str:
    """``%name opcode`` (``:target`` for a custom call) of an HLO line."""
    lhs, _, rhs = hlo.partition(" = ")
    if not rhs:
        return hlo[:80]
    m = re.search(r"(?:^|[\s)\]}])([a-z][a-z0-9\-]*)\(", rhs)
    op = m.group(1) if m else "?"
    t = re.search(r'custom_call_target="([^"]+)"', rhs)
    return f"{lhs} {op}" + (f":{t.group(1)}" if t else "")


def summarize(path: str, window_span: str = SPAN_PREFIX + "window",
              device_prefix: str = "/device:") -> TraceSummary:
    """Reduce one trace. The window is the harness's own ``window_span``;
    a trace without it (a hand recording) spans its first to last device
    op."""
    pd = load_profile(path)
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops = device_ops.setdefault(plane.name, [])
                    for ev in line.events:
                        start = ev.start_ns
                        ops.append((start, start + ev.duration_ns, ev.name))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    win = [(a, b) for a, b, n in spans if n == window_span]
    if win:
        window = win[0]
    else:
        every = [t for ops in device_ops.values() for s, e, *_ in ops
                 for t in (s, e)]
        window = (min(every), max(every)) if every else (0.0, 0.0)
    return TraceSummary(window_ns=window, device_ops=device_ops,
                        spans=sorted(spans))
