"""Device-time attribution from `jax.profiler` dumps (skelly-pulse).

`--profile DIR` wraps a run in a profiler session, which drops an XSpace
protobuf (`*.xplane.pb`) under ``DIR/plugins/profile/<run>/``. That one
file holds everything this module joins, on a TPU and on the CPU alike:

* the executed ops, each with its start and duration. On a TPU they are
  the ``XLA Ops`` line of every ``/device:*`` plane: an event's name is the
  instruction's whole HLO text (``%fusion.17 = ...``), its module is the
  ``XLA Modules`` event that contains it in time, and an op inside a
  ``while`` is an event of its own inside the ``while``'s event. On the CPU
  they are the host-plane events that carry ``hlo_op`` / ``hlo_module``
  stats;
* the optimized HLO of every profiled module on the ``/host:metadata``
  plane, whose per-instruction ``metadata.op_name`` carries the
  `jax.named_scope` path the tracing code declared
  (``jit(step)/.../gmres/arnoldi/precond/fiber/...``) — the hot pipeline
  threads the vocabulary below through every layer (`system/system.py`,
  `solver/gmres.py`, the operators, `ops/kernels.py`, `parallel/`);
* the run loop's own host spans (`obs.tracer.span` enters a
  ``TraceAnnotation("skelly/<path>")``), on the same clock as the ops.

One fold (`load_device_trace`) turns that into self time per scope path,
phase x operator, collectives by kind, and every device idle gap cut at
the edges of the ``skelly/`` spans and put down to the one the host was in. Self time is an event's duration
less its same-line children's; a container (``while``, ``conditional``,
``call``) keeps none of its own, its trips being events already.

**The compile cache serves the first compiler's metadata.** JAX strips
locations, and with them every `named_scope`, from the compile-cache key,
so an executable cached before a scope was added or renamed comes back
with the OLD paths in its HLO. A capture that is going to be folded
therefore compiles with ``jax_compilation_cache_include_metadata_in_key``
set (`include_scopes_in_cache_key`: `profile_session`, `cli.py --profile`
ahead of the build, the benchmark's phase helper), and the fold says
``stale metadata`` where a solve's paths hold no operator scope at all.

No protobuf dependency: the containers are walked with a ~50-line
wire-format reader over the handful of field numbers involved. Unknown
fields are skipped by wire type, so schema growth degrades to missing
metadata (reported as unattributed time), never a crash.

jax-free on purpose (gzip/re only): `obs profile` and `obs timeline`
parse dumps without paying JAX backend init, like `obs summarize`.
"""

from __future__ import annotations

import contextlib
import gzip
import os
import re
from typing import Optional

#: the named_scope phase vocabulary threaded through the hot pipeline.
#: A scope-path component is a PHASE component iff it appears here —
#: everything else in the op_name (jit(...) wrappers, transform scopes,
#: op leaf names) is attribution noise. Grow this set together with the
#: named_scope sites (docs/observability.md "Device-time attribution").
PHASE_SCOPES = frozenset({
    # System step phases (system/system.py, parallel/spmd.py)
    "prep", "gmres", "precond", "refine", "advance",
    # solver phases inside the Krylov loop (solver/gmres.py)
    "arnoldi", "gram", "givens",
    # SPMD collective phases (parallel/spmd.py, parallel/ring.py); the
    # padding of a shell the mesh does not divide (parallel/spmd.py)
    "ring-step", "allgather-density", "psum-dots", "shell_pad",
    # treecode traversal phases (ops/treecode.py)
    "upward", "near", "far",
    # spectral-Ewald pipeline phases (ops/spectral.py; "near" is shared
    # with the treecode vocabulary above)
    "spread", "fft", "kspace", "interp",
    # in-trace auxiliaries: the device DI update (scenarios/di_device.py)
    # and the jitted collision gate (system/system.py)
    "dynamic-instability", "collision",
    # operators, nested under whatever phase calls them: every pair-sum
    # evaluation (ops/kernels.py), the shell, the fiber blocks, the bodies
    "pair", "shell", "fiber", "body",
    # the two dense products below ``fiber`` (fibers/container.py `matvec`
    # / `apply_fiber_force`); ``fiber`` without either is the block
    # preconditioner's application and `prep`'s assembly
    "matvec", "force",
})

#: the operator scopes: every solve applies at least one of them, so a
#: solve whose paths hold none was compiled before they existed
OPERATOR_SCOPES = ("pair", "shell", "fiber", "body")

#: ops that only contain other ops: their trips are events of their own
CONTAINERS = frozenset({"while", "conditional", "call"})

#: every `obs.tracer.span` annotation starts with this
SPAN_PREFIX = "skelly/"

#: HLO collective opcode -> the audit contract's collective kind names
#: (audit/checks.py collective-contract inventory)
COLLECTIVE_KINDS = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "collective-permute": "collective_permute",
    "all-to-all": "all_to_all",
    "reduce-scatter": "reduce_scatter",
    "collective-broadcast": "collective_broadcast",
}


# --------------------------------------------------- protobuf wire reading

def _read_varint(buf, i):
    v = 0
    shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if not b & 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """One message level -> {field_number: [values]} (ints for varints,
    bytes for length-delimited; fixed32/64 skipped). Returns None when the
    buffer does not parse as a protobuf message."""
    i, n = 0, len(buf)
    out: dict = {}
    try:
        while i < n:
            tag, i = _read_varint(buf, i)
            fnum, wtype = tag >> 3, tag & 7
            if fnum == 0 or fnum > 1 << 20:
                return None
            if wtype == 0:
                v, i = _read_varint(buf, i)
                out.setdefault(fnum, []).append(v)
            elif wtype == 2:
                ln, i = _read_varint(buf, i)
                if ln < 0 or i + ln > n:
                    return None
                out.setdefault(fnum, []).append(bytes(buf[i:i + ln]))
                i += ln
            elif wtype == 5:
                i += 4
            elif wtype == 1:
                i += 8
            else:
                return None
    except IndexError:
        return None
    return out


def _utf8(b) -> str:
    try:
        return b.decode("utf-8")
    except (UnicodeDecodeError, AttributeError):
        return ""


def _module_op_names(hlo_module: bytes) -> dict:
    """HloModuleProto bytes -> {instruction name: metadata.op_name}.

    HloModuleProto.computations = field 3 (HloComputationProto),
    HloComputationProto.instructions = field 2 (HloInstructionProto),
    HloInstructionProto.name = field 1, .metadata = field 7 (OpMetadata),
    OpMetadata.op_name = field 2 — the named_scope path."""
    out: dict = {}
    mod = _fields(hlo_module)
    if not mod:
        return out
    for comp_b in mod.get(3, []):
        comp = _fields(comp_b)
        if not comp:
            continue
        for instr_b in comp.get(2, []):
            instr = _fields(instr_b)
            if not instr or 1 not in instr or 7 not in instr:
                continue
            name = _utf8(instr[1][0])
            meta = _fields(instr[7][0])
            if not name or not meta or 2 not in meta:
                continue
            op_name = _utf8(meta[2][0])
            if op_name:
                out[name] = op_name
    return out


def _map_entries(entries):
    """A protobuf ``map<int64, Message>`` field -> [(key, message fields)]."""
    out = []
    for entry_b in entries:
        entry = _fields(entry_b)
        if not entry or 2 not in entry:
            continue
        msg = _fields(entry[2][0])
        if msg:
            out.append((entry.get(1, msg.get(1, [0]))[0], msg))
    return out


def _read_planes(xplane_path: str) -> list:
    """XSpace.planes (field 1) of a ``.xplane.pb`` (or ``.xplane.pb.gz``),
    one field dict each; [] on any structural surprise."""
    opener = gzip.open if xplane_path.endswith(".gz") else open
    with opener(xplane_path, "rb") as fh:
        space = _fields(fh.read())
    if not space:
        return []
    return [p for p in map(_fields, space.get(1, [])) if p]


def _hlo_scopes(planes: list) -> dict:
    """{``module(program_id)``: {instruction name: scope path}}.

    The profiler stores each profiled module's optimized `HloProto` as a
    bytes stat (stat-metadata name ``Hlo Proto``) on the ``/host:metadata``
    plane's event metadata (XPlane.event_metadata = field 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5; XStat
    .metadata_id = 1, .bytes_value = 6); the event-metadata name is
    ``module_name(program_id)``, as an ``XLA Modules`` event is named."""
    out: dict = {}
    for plane in planes:
        hlo_stat_ids = {key for key, meta in _map_entries(plane.get(5, []))
                        if _utf8(meta.get(2, [b""])[0]) == "Hlo Proto"}
        if not hlo_stat_ids:
            continue
        for _, emeta in _map_entries(plane.get(4, [])):
            name = _utf8(emeta.get(2, [b""])[0])
            for stat_b in emeta.get(5, []):
                stat = _fields(stat_b)
                if (not stat or stat.get(1, [None])[0] not in hlo_stat_ids
                        or 6 not in stat):
                    continue
                hlo = _fields(stat[6][0])
                if hlo and 1 in hlo:
                    out.setdefault(name, {}).update(
                        _module_op_names(hlo[1][0]))
    return out


def _bare(module: str) -> str:
    """``jit_f(5)`` -> ``jit_f``."""
    return module.rsplit("(", 1)[0]


def load_op_name_map(xplane_path: str) -> dict:
    """{(module_name, instruction_name): scope path} from an xplane dump,
    module names without their program id. Degrades to {} on any structural
    surprise — callers then report the time as unattributed, never crash."""
    return {(_bare(module), instr): op_name
            for module, names in _hlo_scopes(_read_planes(xplane_path)).items()
            for instr, op_name in names.items()}


# ------------------------------------------------------------ event reading

def find_xplanes(profile_dir: str) -> list:
    """The ``.xplane.pb`` files of the LATEST run under a profiler dump
    dir (``DIR/plugins/profile/<run>/``); a run dir itself, or one dump
    file (``.xplane.pb`` / ``.xplane.pb.gz``), works too."""
    if os.path.isfile(profile_dir):
        return [profile_dir]
    cand = profile_dir
    runs_root = os.path.join(profile_dir, "plugins", "profile")
    if os.path.isdir(runs_root):
        runs = sorted(d for d in os.listdir(runs_root)
                      if os.path.isdir(os.path.join(runs_root, d)))
        if not runs:
            return []
        cand = os.path.join(runs_root, runs[-1])
    if not os.path.isdir(cand):
        return []
    return [os.path.join(cand, f) for f in sorted(os.listdir(cand))
            if f.endswith((".xplane.pb", ".xplane.pb.gz"))]


_OPCODE = re.compile(r"(?:^|[\s)\]}])([a-z][a-z0-9\-]*)\(")


def _instruction(text: str):
    """(instruction name, opcode) of an op event's name: a TPU event is
    named by its whole HLO line, a CPU event by the instruction alone."""
    lhs, sep, rhs = text.partition(" = ")
    if sep and lhs.startswith("%"):
        m = _OPCODE.search(rhs)
        return lhs[1:], (m.group(1) if m else "")
    return text, text.split(".")[0]


def _stat_value(stat: dict, stat_names: dict):
    """XStat value: uint64 = 3, int64 = 4, str = 5, ref (a stat-metadata
    name) = 7; doubles are not read."""
    if 3 in stat:
        return stat[3][0]
    if 4 in stat:
        v = stat[4][0]
        return v - (1 << 64) if v >= 1 << 63 else v
    if 5 in stat:
        return _utf8(stat[5][0])
    if 7 in stat:
        return stat_names.get(stat[7][0], "")
    return None


def _plane_events(plane: dict):
    """(ops, spans) of one plane, times in picoseconds on the dump's clock
    (XLine.timestamp_ns = field 3, .events = 4; XEvent.metadata_id = 1,
    .offset_ps = 2, .duration_ps = 3, .stats = 4).

    ops: dicts with name (the instruction), opcode, module, ts, dur, pid
    (the plane), tid (the line). spans: (start, end, name, stats) of every
    event named ``skelly/...``."""
    plane_name = _utf8(plane.get(2, [b""])[0])
    stat_names = {k: _utf8(m.get(2, [b""])[0])
                  for k, m in _map_entries(plane.get(5, []))}
    meta_names = {k: _utf8(m.get(2, [b""])[0])
                  for k, m in _map_entries(plane.get(4, []))}
    on_device = plane_name.startswith("/device:")
    hlo_stats = {"hlo_op", "hlo_module"} & set(stat_names.values())

    def stats_of(ev):
        out = {}
        for stat_b in ev.get(4, []):
            stat = _fields(stat_b)
            if stat and 1 in stat:
                out[stat_names.get(stat[1][0], "")] = _stat_value(
                    stat, stat_names)
        return out

    ops, spans, modules = [], [], []
    instr_memo: dict = {}
    for line_b in plane.get(3, []):
        line = _fields(line_b)
        if not line:
            continue
        line_name = _utf8(line.get(2, [b""])[0])
        if on_device and line_name not in ("XLA Ops", "XLA Modules"):
            continue
        base_ps = line.get(3, [0])[0] * 1000
        for ev_b in line.get(4, []):
            ev = _fields(ev_b)
            if not ev or 1 not in ev:
                continue
            name = meta_names.get(ev[1][0], "")
            ts = base_ps + ev.get(2, [0])[0]
            dur = ev.get(3, [0])[0]
            if on_device:
                if line_name == "XLA Modules":
                    modules.append((ts, ts + dur, name))
                    continue
                if name not in instr_memo:
                    instr_memo[name] = _instruction(name)
                instr, opcode = instr_memo[name]
                ops.append({"name": instr, "opcode": opcode, "module": None,
                            "ts": ts, "dur": dur, "pid": plane_name,
                            "tid": line_name})
            elif name.startswith(SPAN_PREFIX):
                spans.append((ts, ts + dur, name, stats_of(ev)))
            elif hlo_stats:
                st = stats_of(ev)
                if "hlo_op" not in st and "hlo_module" not in st:
                    continue
                instr = st.get("hlo_op") or name
                module = st.get("hlo_module") or "?"
                if st.get("program_id") is not None:
                    module = f"{module}({st['program_id']})"
                ops.append({"name": instr, "opcode": instr.split(".")[0],
                            "module": module, "ts": ts, "dur": dur,
                            "pid": plane_name, "tid": line_name})
    # a TPU op's module is the `XLA Modules` event that contains it in time
    modules.sort()
    ops.sort(key=lambda e: e["ts"])
    i = 0
    for e in ops:
        if e["module"] is not None:
            continue
        while i < len(modules) and modules[i][1] <= e["ts"]:
            i += 1
        inside = i < len(modules) and modules[i][0] <= e["ts"]
        e["module"] = modules[i][2] if inside else "?"
    return ops, spans


def _self_times(events: list) -> None:
    """Sets ``self`` on each event: its duration minus its same-line
    children's, so nested events (a ``while`` around its trips) never
    double-count."""
    by_line: dict = {}
    for e in events:
        by_line.setdefault((e["pid"], e["tid"]), []).append(e)
    for evs in by_line.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list = []   # open events, outermost first
        for e in evs:
            e["self"] = e["dur"]
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
                stack.pop()
            if stack:
                stack[-1]["self"] -= e["dur"]
            stack.append(e)
    for e in events:
        e["self"] = max(e["self"], 0)


def phase_of(op_name: str) -> Optional[str]:
    """Slash-joined RECOGNIZED scope components of a metadata op_name, or
    None — ``jit(step)/.../gmres/precond/dot_general`` -> ``gmres/precond``.
    Each component once, where it first appears: a scope re-entered per
    ring hop, or a name stack that JAX repeats under ``vmap`` of a ``jit``,
    adds nothing. JAX names a loop's body ``while/body``: that ``body`` is
    no scope of ours. XLA joins the names of instructions it merged with
    ``;``: the first counts."""
    comps = []
    prev = ""
    for c in op_name.split(";", 1)[0].split("/"):
        if (c in PHASE_SCOPES and c not in comps
                and not (c == "body" and prev == "while")):
            comps.append(c)
        prev = c
    return "/".join(comps) if comps else None


def collective_kind(op_event_name: str) -> Optional[str]:
    """``all-reduce.17`` -> ``all_reduce`` (audit-contract spelling).

    Prefix-matches past the opcode so the TPU lowering's async pairs
    (``all-reduce-start.N`` / ``all-reduce-done.N``) and fused collective
    thunks (``all-reduce-fusion``) classify as their kind too — on real
    chips EVERY collective is async, and missing them would file all comm
    time under "(computation)"."""
    base = op_event_name.split(".")[0].split(" ")[0]
    for opcode, kind in COLLECTIVE_KINDS.items():
        if base == opcode or base.startswith(opcode + "-"):
            return kind
    return None


def operator_of(phase: Optional[str]) -> str:
    """The operator components of a phase path, slash-joined
    (``gmres/arnoldi/precond/fiber`` -> ``fiber``, ``prep/shell/pair`` ->
    ``shell/pair``), or ``-`` for a path outside every operator."""
    ops = [c for c in (phase or "").split("/") if c in OPERATOR_SCOPES]
    return "/".join(ops) or "-"


def step_phase_of(phase: Optional[str]) -> str:
    """The step phase a path belongs to — ``refine`` wherever it appears
    (the f64 residual sweeps run inside ``gmres``), else the outermost
    component — or ``(unattributed)``."""
    comps = (phase or "").split("/")
    if "refine" in comps:
        return "refine"
    return comps[0] or "(unattributed)"


class DeviceTrace:
    """Aggregated per-op device time from one profile dump.

    ``rows`` is a list of dicts, one per instruction and plane: pid (the
    plane: one chip), op (instruction name), module, phase
    (recognized scope path or None), collective (kind or None), scope (the
    full metadata op_name when known), dur_us (summed SELF time), count.
    The totals and tables sum over the planes; `plane_seconds` and
    `plane_table` keep the chips of a mesh run apart.
    ``events`` keeps the raw per-execution op events (ts/dur/self_us in
    microseconds on the dump's clock, phase, ...) for the timeline
    renderer; ``spans`` the run loop's ``skelly/`` annotations as
    (start_us, end_us, path, stats); ``window_us`` what was folded.
    ``stale`` is True where the executed modules' paths name a ``gmres``
    phase and no operator scope: metadata from before the operator scopes,
    served by the compile cache.
    """

    def __init__(self, rows: list, events: list, spans: list = (),
                 window_us=None, stale: bool = False):
        self.rows = rows
        self.events = events
        self.spans = list(spans)
        self.window_us = window_us
        self.stale = stale

    # ------------------------------------------------------------- totals

    @property
    def total_us(self) -> float:
        return sum(r["dur_us"] for r in self.rows)

    @property
    def attributed_us(self) -> float:
        return sum(r["dur_us"] for r in self.rows if r["phase"])

    @property
    def inferred_us(self) -> float:
        return sum(r["dur_us"] for r in self.rows
                   if r["phase"] and r.get("inferred"))

    @property
    def attributed_frac(self) -> float:
        tot = self.total_us
        return (self.attributed_us / tot) if tot > 0 else 0.0

    def seconds(self, has=(), lacks=()) -> Optional[float]:
        """Self time, in seconds, of the ops whose phase path holds every
        component of ``has`` and none of ``lacks``; None where no op's
        path holds ``has`` at all (not seen is not zero)."""
        total, seen = 0.0, False
        for r in self.rows:
            comps = (r["phase"] or "").split("/")
            if all(c in comps for c in has):
                seen = True
                if not any(c in comps for c in lacks):
                    total += r["dur_us"]
        return total * 1e-6 if seen else None

    # ------------------------------------------------------------ per chip

    @property
    def planes(self) -> list:
        """The planes that ran ops, sorted: one per chip on a TPU."""
        return sorted({r["pid"] for r in self.rows})

    def plane_seconds(self, has=(), lacks=(), collective=None) -> dict:
        """{plane: self seconds} of the ops `seconds` would count, for each
        plane that ran any op (0.0 where a chip ran none of these).
        ``collective=True`` keeps the collective ops alone (permutes,
        all-reduces, all-gathers: the exchange itself)."""
        out = {pid: 0.0 for pid in self.planes}
        for r in self.rows:
            comps = (r["phase"] or "").split("/")
            if (all(c in comps for c in has)
                    and not any(c in comps for c in lacks)
                    and (collective is None
                         or bool(r["collective"]) == collective)):
                out[r["pid"]] += r["dur_us"] * 1e-6
        return out

    def plane_table(self) -> list:
        """One row a chip: busy, op self time, and the mesh's own scopes
        and collectives, in seconds."""
        ring = self.plane_seconds(has=("ring-step",))
        psum = self.plane_seconds(has=("psum-dots",))
        coll = self.plane_seconds(collective=True)
        total = self.plane_seconds()
        return [{"plane": pid,
                 "busy_s": sum(e - s for s, e in
                               self.busy_intervals(pid)) * 1e-6,
                 "op_self_s": total[pid], "ring_step_s": ring[pid],
                 "psum_dots_s": psum[pid], "collective_s": coll[pid]}
                for pid in self.planes]

    def _group(self, key_fn) -> list:
        groups: dict = {}
        for r in self.rows:
            key = key_fn(r)
            g = groups.setdefault(key, {"dur_us": 0.0, "count": 0,
                                        "collectives": {}})
            g["dur_us"] += r["dur_us"]
            g["count"] += r["count"]
            if r["collective"]:
                g["collectives"][r["collective"]] = (
                    g["collectives"].get(r["collective"], 0.0) + r["dur_us"])
        tot = self.total_us
        out = []
        for key, g in groups.items():
            out.append({"key": key, "dur_us": round(g["dur_us"], 3),
                        "count": g["count"],
                        "share": (g["dur_us"] / tot) if tot > 0 else 0.0,
                        "collectives": {k: round(v, 3) for k, v
                                        in sorted(g["collectives"].items())}})
        out.sort(key=lambda r: -r["dur_us"])
        return out

    def by_phase(self) -> list:
        """Per-phase totals; unattributed time reported under the explicit
        ``(unattributed)`` key, never hidden."""
        return self._group(lambda r: r["phase"] or "(unattributed)")

    def by_collective(self) -> list:
        """Collectives by kind + one ``(computation)`` row for the rest —
        the comm/compute split the CA-GMRES ladder work tunes against."""
        return self._group(lambda r: r["collective"] or "(computation)")

    def by_op(self) -> list:
        return self._group(lambda r: f"{r['module']}/{r['op']}")

    def cross_table(self) -> dict:
        """{step phase: {operator: seconds}} — `step_phase_of` x
        `operator_of` over every row's self time."""
        table: dict = {}
        for r in self.rows:
            row = table.setdefault(step_phase_of(r["phase"]), {})
            op = operator_of(r["phase"])
            row[op] = row.get(op, 0.0) + r["dur_us"] * 1e-6
        return table

    # ---------------------------------------------------------- idle gaps

    def busy_intervals(self, pid=None) -> list:
        """The union of one device's op intervals (the first device's by
        default), clipped to the window: sorted [start_us, end_us]."""
        pid = pid if pid is not None else min(
            (e["pid"] for e in self.events), default=None)
        merged: list = []
        for s, e in sorted((e["ts"], e["ts"] + e["dur"])
                           for e in self.events if e["pid"] == pid):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    @property
    def busy_us(self) -> float:
        return sum(e - s for s, e in self.busy_intervals())

    def idle_gaps(self, min_us: float = 0.0) -> list:
        """The first device's idle time inside the window, put down to the
        run loop: each gap between busy intervals (those longer than
        ``min_us``) is cut at the edges of the ``skelly/`` spans it
        crosses, and each piece carries the innermost span around it
        (without the prefix; ``-`` outside every span). Longest first:
        (start_us, end_us, label)."""
        if not self.events or self.window_us is None:
            return []
        lo, hi = self.window_us
        gaps, cur = [], lo
        for s, e in self.busy_intervals():
            if s - cur > min_us:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi - cur > min_us:
            gaps.append((cur, hi))
        out = []
        for s, e in gaps:
            around = [sp for sp in self.spans if sp[0] < e and sp[1] > s]
            edges = sorted({s, e} | {t for a, b, _, _ in around
                                     for t in (a, b) if s < t < e})
            for u, v in zip(edges, edges[1:]):
                inner = min(((b - a, path) for a, b, path, _ in around
                             if a <= u and v <= b), default=None)
                out.append((u, v, inner[1][len(SPAN_PREFIX):] if inner
                            else "-"))
        out.sort(key=lambda g: g[0] - g[1])
        return out

    def gap_table(self, min_us: float = 0.0) -> list:
        """`idle_gaps` summed by label, pieces shorter than ``min_us`` left
        out: [{label, count, ms}], largest first."""
        table: dict = {}
        for s, e, label in self.idle_gaps(min_us):
            if e - s < min_us:
                continue
            row = table.setdefault(label, {"label": label, "count": 0,
                                           "ms": 0.0})
            row["count"] += 1
            row["ms"] += (e - s) * 1e-3
        return sorted(table.values(), key=lambda r: -r["ms"])

    def idle_us_inside(self, path: str) -> float:
        """Device idle time inside the spans named ``path`` (``skelly/run``)
        and their children, in microseconds."""
        name = path[len(SPAN_PREFIX):]
        return sum(e - s for s, e, label in self.idle_gaps()
                   if label == name or label.startswith(name + "/"))


def load_device_trace(profile_dir: str, window=None) -> DeviceTrace:
    """Fold a profiler dump (a `--profile DIR`, a run dir, or one
    ``.xplane.pb[.gz]``) into a `DeviceTrace`.

    ``window`` = ``(t0_ns, t1_ns)`` on the dump's own clock (what
    `jax.profiler.ProfileData` calls ``start_ns``) clips every op to it and
    drops the spans outside; without one the fold spans the first to the
    last op. Scope
    paths come from the HLO metadata in the same file; an op whose
    instruction misses there is unattributed, or inferred from its
    neighbours (`_infer_gap_phases`). Raises FileNotFoundError when there
    is no dump to read."""
    xplanes = find_xplanes(profile_dir)
    if not xplanes:
        raise FileNotFoundError(
            f"no *.xplane.pb under {profile_dir!r} — is this a "
            "`--profile DIR` dump (DIR/plugins/profile/<run>/)?")
    scopes: dict = {}
    events, spans = [], []
    for xp in xplanes:
        planes = _read_planes(xp)
        scopes.update(_hlo_scopes(planes))
        for plane in planes:
            ops, sp = _plane_events(plane)
            events.extend(ops)
            spans.extend(sp)
    # module names without a program id serve events that carry none
    for module, names in list(scopes.items()):
        scopes.setdefault(_bare(module), names)

    if window is not None:
        lo, hi = (int(round(t * 1000)) for t in window)
    elif events:
        lo = min(e["ts"] for e in events)
        hi = max(e["ts"] + e["dur"] for e in events)
    else:
        lo = hi = 0
    clipped = []
    for e in events:
        s, t = max(e["ts"], lo), min(e["ts"] + e["dur"], hi)
        if t > s or (e["dur"] == 0 and lo <= e["ts"] <= hi):
            e["ts"], e["dur"] = s, t - s
            clipped.append(e)
    events = clipped
    if window is not None:      # host spans stay whole: they only label
        spans = [sp for sp in spans if sp[1] > lo and sp[0] < hi]
    _self_times(events)

    ran: dict = {}     # module -> its {instruction: scope path}
    phases: dict = {"": None}      # scope path -> phase, each folded once
    for e in events:
        names = ran.get(e["module"])
        if names is None:
            names = ran[e["module"]] = (scopes.get(e["module"])
                                        or scopes.get(_bare(e["module"]), {}))
        scope = names.get(e["name"], "")
        if scope not in phases:
            phases[scope] = phase_of(scope)
        e["scope"] = scope
        e["phase"] = phases[scope]
        e["inferred"] = False
        e["collective"] = collective_kind(e["name"])
        if e.pop("opcode") in CONTAINERS:
            e["self"] = 0
        # microseconds from here on (the timeline's and the tables' unit)
        e["ts"], e["dur"] = e["ts"] * 1e-6, e["dur"] * 1e-6
        e["self_us"] = e.pop("self") * 1e-6
        e["op"] = e["name"]
    _infer_gap_phases(events)
    declared = {c for names in ran.values() for path in names.values()
                for c in (phase_of(path) or "").split("/")}
    stale = "gmres" in declared and not declared & set(OPERATOR_SCOPES)

    # a row is one instruction on one plane (one chip): the totals below sum
    # over the planes, `DeviceTrace.plane_seconds` keeps the chips apart
    agg: dict = {}
    for e in events:
        key = (e["pid"], e["module"], e["op"], e["phase"])
        row = agg.setdefault(key, {
            "pid": e["pid"],
            "op": e["op"], "module": e["module"], "phase": e["phase"],
            "inferred": e["inferred"], "collective": e["collective"],
            "scope": e["scope"], "dur_us": 0.0, "count": 0})
        row["dur_us"] += e["self_us"]
        row["count"] += 1
    rows = sorted(agg.values(), key=lambda r: -r["dur_us"])
    for r in rows:
        r["dur_us"] = round(r["dur_us"], 6)
    return DeviceTrace(rows, events,
                       [(a * 1e-6, b * 1e-6, p, st) for a, b, p, st in spans],
                       (lo * 1e-6, hi * 1e-6), stale)


def _infer_gap_phases(events: list) -> None:
    """Temporal-locality gap fill for metadata-less ops.

    XLA optimization renames/expands instructions (fusions, the
    triangular-solve while+dot expansion) whose names then miss the
    xplane HLO's pre-optimization metadata. The device thread executes
    serially in phase-contiguous segments, so an unmatched op whose
    nearest metadata-attributed neighbors ON BOTH SIDES (same thread)
    agree on a phase almost surely belongs to it: inherit, and mark the
    event ``inferred`` so the table reports directly-attributed and
    inferred shares separately (never silently)."""
    by_tid: dict = {}
    for e in events:
        by_tid.setdefault((e["pid"], e["tid"]), []).append(e)
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        # prev_phase[i]: phase of the nearest attributed event at or before i
        n = len(evs)
        prev_ph = [None] * n
        last = None
        for i, e in enumerate(evs):
            if e["phase"]:
                last = e["phase"]
            prev_ph[i] = last
        nxt = None
        for i in range(n - 1, -1, -1):
            e = evs[i]
            if e["phase"]:
                nxt = e["phase"]
            elif prev_ph[i] is not None and prev_ph[i] == nxt:
                e["phase"] = nxt
                e["inferred"] = True


# -------------------------------------------------------------- rendering

def _columns(rows: list) -> list:
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    return ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
            for r in rows]


def _render_cross(trace: DeviceTrace) -> list:
    table = trace.cross_table()
    cols = sorted({op for row in table.values() for op in row},
                  key=lambda op: -sum(r.get(op, 0.0) for r in table.values()))
    rows = [("phase \\ operator (ms)", *cols, "total")]
    for phase, row in sorted(table.items(),
                             key=lambda kv: -sum(kv[1].values())):
        rows.append((phase, *(f"{row.get(c, 0.0) * 1e3:.3f}" for c in cols),
                     f"{sum(row.values()) * 1e3:.3f}"))
    return _columns(rows)


def _render_planes(trace: DeviceTrace) -> list:
    """The per-chip lines of a mesh run's report (none for one plane): the
    tables above SUM over the chips; the slowest chip sets the step."""
    table = trace.plane_table()
    if len(table) < 2:
        return []
    cols = ("busy_s", "op_self_s", "ring_step_s", "psum_dots_s",
            "collective_s")
    rows = [("chip", *(c[:-2] + "_ms" for c in cols))]
    for r in table:
        rows.append((r["plane"], *(f"{r[c] * 1e3:.3f}" for c in cols)))
    means = {c: sum(r[c] for r in table) / len(table) for c in cols}
    rows.append(("mean", *(f"{means[c] * 1e3:.3f}" for c in cols)))
    rows.append(("spread (max-min)/mean", *(
        f"{(max(r[c] for r in table) - min(r[c] for r in table)) / means[c]:.1%}"
        if means[c] > 0 else "-" for c in cols)))
    return ["", f"per chip ({len(table)} device planes; the tables above sum "
            "over them):"] + _columns(rows)


def render_table(trace: DeviceTrace, by: str = "phase") -> str:
    """The `obs profile` text report (docs/observability.md)."""
    if by == "cross":
        out = _render_cross(trace)
    else:
        groups = {"phase": trace.by_phase, "collective": trace.by_collective,
                  "op": trace.by_op}[by]()
        rows = [(by, "time_ms", "share", "ops", "collectives")]
        for g in groups[:40]:
            colls = "  ".join(f"{k}={v / 1e3:.3f}ms"
                              for k, v in g["collectives"].items())
            rows.append((str(g["key"]), f"{g['dur_us'] / 1e3:.3f}",
                         f"{g['share']:.1%}", str(g["count"]), colls))
        out = _columns(rows)
        if len(groups) > 40:
            out.append(f"... ({len(groups) - 40} more rows; --json for all)")
    out.append("")
    tot = trace.total_us
    inf_frac = (trace.inferred_us / tot) if tot > 0 else 0.0
    out.append(f"device op time: {tot / 1e3:.3f}ms over "
               f"{sum(r['count'] for r in trace.rows)} op executions; "
               f"{trace.attributed_frac:.1%} attributed to named phases "
               f"({trace.attributed_frac - inf_frac:.1%} via HLO metadata, "
               f"{inf_frac:.1%} inferred from phase-contiguous neighbors)")
    if trace.stale:
        out.append("stale metadata: the solve's scope paths name no operator "
                   f"({', '.join(OPERATOR_SCOPES)}); the compile cache "
                   "served executables from before those scopes "
                   "(docs/observability.md \"The cache and the scopes\")")
    out.extend(_render_planes(trace))
    gaps = trace.gap_table(min_us=100.0)
    if trace.spans and gaps:
        out.append("")
        out.append("device idle time by run-loop span (pieces of 0.1 ms or "
                   "more):")
        out.extend(_columns([("span", "gaps", "ms")] + [
            (g["label"], str(g["count"]), f"{g['ms']:.3f}") for g in gaps]))
    return "\n".join(out) + "\n"


def profile_json(trace: DeviceTrace) -> dict:
    return {
        "total_us": round(trace.total_us, 3),
        "busy_us": round(trace.busy_us, 3),
        "attributed_us": round(trace.attributed_us, 3),
        "inferred_us": round(trace.inferred_us, 3),
        "attributed_frac": round(trace.attributed_frac, 4),
        "stale_metadata": trace.stale,
        "by_phase": trace.by_phase(),
        "by_collective": trace.by_collective(),
        "by_op": trace.by_op(),
        "phase_by_operator": trace.cross_table(),
        "idle_gaps": trace.gap_table(),
        "per_plane": trace.plane_table(),
    }


# ---------------------------------------------------------- capture context

def include_scopes_in_cache_key(on: bool = True) -> bool:
    """Make `jax.named_scope` paths part of the compile-cache key; returns
    what the setting was. JAX leaves locations out of the key, so a
    persistent cache serves whatever metadata the FIRST compile of a
    program had: a scope added or renamed since is invisible in the HLO of
    every later run that hits that entry. Call before the first compile of
    anything a capture is going to fold; an executable already in memory
    keeps the metadata it was built or loaded with."""
    import jax

    was = bool(jax.config.jax_compilation_cache_include_metadata_in_key)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      bool(on))
    return was


@contextlib.contextmanager
def _capture(profile_dir: str):
    """The profiler session itself: Python tracer off, HLO protos on; plain
    `jax.profiler.trace` where the options API is unavailable."""
    import jax

    try:
        from jax._src.lib import xla_client

        import jax.extend.backend as jax_backend

        jax_backend.get_backend()   # TPU tracer needs an initialized backend
        opts = xla_client.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = True
        sess = xla_client.profiler.ProfilerSession(opts)
    except Exception:
        with jax.profiler.trace(str(profile_dir)):
            yield
        return
    try:
        yield
    finally:
        sess.export(sess.stop(), str(profile_dir))


@contextlib.contextmanager
def profile_session(profile_dir: str):
    """Profiler capture tuned for device-time attribution.

    `jax.profiler.trace` captures Python host frames too
    (``python_tracer_level=1``); around a loop that COMPILES inside the
    window, those frames flood the ~1M-event trace buffer and evict the
    device op events this parser needs (observed: a 2-step `System.run`
    produced 1,000,027 events with ZERO surviving ``hlo_op`` args). This
    context creates the profiler session with the Python tracer OFF and
    ``enable_hlo_proto`` on — host-side timing is the span tracer's job
    (its ``skelly/`` annotations land in the same dump), the profiler's is
    the device. Programs compiled inside the session carry the scopes the
    running source declares (`include_scopes_in_cache_key`).

    jax imports stay inside the context so module import remains jax-free.
    """
    was = include_scopes_in_cache_key()
    try:
        with _capture(profile_dir):
            yield
    finally:
        include_scopes_in_cache_key(was)


# ------------------------------------------------- telemetry-stream bridge

def device_phase_events(profile_dir: str) -> list:
    """The ``device_phase`` telemetry records for a profile dump: one per
    phase (incl. ``(unattributed)``) with ``dur_s``/``share``/``ops`` and
    the per-kind collective split. Appended to the run's `--trace-file` by
    the CLIs so `obs summarize` renders device time next to host spans."""
    trace = load_device_trace(profile_dir)
    out = []
    for g in trace.by_phase():
        out.append({"phase": g["key"], "dur_s": round(g["dur_us"] / 1e6, 6),
                    "share": round(g["share"], 4), "ops": g["count"],
                    "collectives": {k: round(v / 1e6, 6)
                                    for k, v in g["collectives"].items()},
                    "stale_metadata": trace.stale})
    return out


def emit_device_phases(profile_dir: str, tracer=None) -> int:
    """Parse ``profile_dir`` and emit one ``device_phase`` event per phase
    into ``tracer`` (or the process-active tracer). Returns the number of
    events emitted; swallows parse errors (a broken profiler dump must
    never fail the run that produced it) but NOT tracer write errors."""
    from . import tracer as obs_tracer

    tr = tracer if tracer is not None else obs_tracer.active()
    if tr is None:
        return 0
    try:
        events = device_phase_events(profile_dir)
    except Exception as e:
        tr.emit("device_phase_error", error=f"{type(e).__name__}: {e}",
                profile_dir=str(profile_dir))
        return 0
    for rec in events:
        tr.emit("device_phase", **rec)
    return len(events)
