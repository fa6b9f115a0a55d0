"""Continuous-batching scheduler: queue -> lanes -> retire -> backfill.

The host-side half of the ensemble subsystem, shaped like an inference
server's batch scheduler: a fixed number of compiled lanes B, a work queue
of pending members, and a drain loop that steps the whole batch, writes
per-member trajectory frames at dt_write boundaries, retires members that
reach their ``t_final``, and immediately backfills freed lanes from the
queue — pure leaf substitution at fixed shapes (`runner.set_lane`), so a
10k-member sweep streams through ONE compiled program
(`testing.trace_counting_jit` pins the single trace in
tests/test_ensemble.py).

The per-step host work is one small device fetch (the [B] outcome vectors in
`EnsembleStepInfo`) plus frame encodes for whichever members crossed a write
boundary; the solves themselves never leave the device.
"""

from __future__ import annotations

import dataclasses
import logging
import time as _time
from collections import deque
from typing import Callable, Optional

import numpy as np

from ..guard import verdict as _verdict
from ..obs import flight as flight_mod
from ..obs import tracer as obs_tracer
from ..solver.gmres import history_rows
from ..system.system import (SimState, crossed_write_boundary,
                             reached_t_final)
from ..utils.rng import SimRNG
from .runner import EnsembleRunner, lane_state, rng_carry, set_lane

logger = logging.getLogger("skellysim_tpu")

#: ensemble t_final for an empty lane: `time < -inf` is never true, so idle
#: lanes are inert masked no-ops until the queue refills them
IDLE_T_FINAL = float("-inf")


def _fiber_capacity(state) -> int:
    """Total fiber-slot count of a member state (the growth-event id)."""
    from ..fibers import container as fc

    return sum(g.n_fibers for g in fc.as_buckets(state.fibers))


@dataclasses.dataclass
class MemberSpec:
    """One queued simulation: initial state + end time (+ optional RNG whose
    dump rides in the member's trajectory frames, `SimRNG.member(i)`)."""

    member_id: str
    state: SimState
    t_final: float
    rng: Optional[SimRNG] = None
    #: perf_counter timestamp of queue entry (stamped by the scheduler when
    #: absent); lane events report ``queue_wait_s`` — admission latency,
    #: the serving SLO — from it
    enqueued_at: Optional[float] = None


@dataclasses.dataclass
class _Lane:
    spec: MemberSpec
    steps: int = 0       # trial steps taken (accepted + rejected)
    frames: int = 0      # frames written (excluding the initial frame)
    t: float = 0.0       # entry time of the NEXT trial
    dt: float = 0.0      # entry dt of the NEXT trial


class EnsembleScheduler:
    """Drain a member queue through B compiled lanes.

    ``writer(member_id, state, rng_state=None)`` is called for each frame a
    member crosses (`io.ensemble_io.MemberTrajectoryWriters` is the
    file-based implementation; any callable works). ``metrics`` is a
    callable receiving one dict per record (`io.ensemble_io
    .EnsembleMetricsWriter.write`); record kinds are "start", "step",
    "retire", and "dt_underflow" (schema in docs/ensemble.md).

    ``step_fn`` overrides the runner's jit'd step — the trace-counting tests
    pass `testing.trace_counting_jit(runner.step_impl)` here.

    ``on_dt_underflow``: the sequential loop raises RuntimeError when the
    adaptive dt falls below dt_min; "raise" (default) mirrors that,
    "retire" retires just the failing member (recorded in metrics) and keeps
    the rest of the sweep running — the serving-shaped choice for large
    sweeps.

    ``on_failure``: what to do with a lane the runner quarantined on a
    TERMINAL health verdict (`EnsembleStepInfo.failed` — a nonfinite
    state no retry can repair; docs/robustness.md). "raise" (default)
    mirrors the sequential loop's eventual abort; "retire" (skelly-serve)
    retires just that member with reason ``"failed"`` — its metrics
    record and `on_retire` callback carry the decoded verdict, and its
    siblings' trajectories are bitwise-unaffected (the quarantine pin in
    tests/test_ensemble.py).

    ``template`` allows an INITIALLY-EMPTY scheduler (``members=[]``): a
    long-lived service (skelly-serve) constructs the compiled lanes before
    any tenant exists, then feeds them incrementally via `admit` + `poll`.
    The template state defines the lanes' static shapes — the capacity
    bucket every later member must match.

    ``on_retire(member_id, state, reason, **extra)`` receives the member's
    FINAL lane state the moment before its lane is freed — the exact
    snapshot point (possibly newer than its last dt_write frame);
    skelly-serve stores it for tenant snapshot/resume. ``extra`` carries
    structured failure context (``health``/``verdict``) on ``failed`` and
    ``dt_underflow`` retirements, plus ``rng_state`` (the member's current
    serialized RNG streams) whenever the member carries a `SimRNG`.

    ``on_growth``: what to do with a lane whose device dynamic-instability
    update reported ``needs_growth`` (the member's nucleation burst
    outgrew its capacity bucket; the runner froze the lane un-advanced).
    "raise" (default) aborts; "retire" retires the member with reason
    ``"growth"`` — its CURRENT state and synced RNG ride the `on_retire`
    callback, and the caller (scenarios.sweep, skelly-serve) re-admits it
    onto the next capacity rung (docs/scenarios.md "Growth reseats").
    """

    def __init__(self, runner: EnsembleRunner, members, batch: int, *,
                 writer: Optional[Callable] = None,
                 metrics: Optional[Callable] = None,
                 step_fn: Optional[Callable] = None,
                 write_initial_frames: bool = False,
                 on_dt_underflow: str = "raise",
                 on_failure: str = "raise",
                 on_growth: str = "raise",
                 max_rounds: Optional[int] = None,
                 template: Optional[SimState] = None,
                 on_retire: Optional[Callable] = None):
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        if on_dt_underflow not in ("raise", "retire"):
            raise ValueError(
                f"unknown on_dt_underflow {on_dt_underflow!r}; "
                "use 'raise' or 'retire'")
        if on_failure not in ("raise", "retire"):
            raise ValueError(
                f"unknown on_failure {on_failure!r}; use 'raise' or 'retire'")
        if on_growth not in ("raise", "retire"):
            raise ValueError(
                f"unknown on_growth {on_growth!r}; use 'raise' or 'retire'")
        members = list(members)
        if not members and template is None:
            raise ValueError("ensemble needs at least one member (or a "
                             "template= state for an initially-empty service)")
        self.runner = runner
        self.batch = batch
        self.queue = deque()
        self.writer = writer
        self.metrics = metrics
        self.step_fn = step_fn or runner.step
        self.write_initial_frames = write_initial_frames
        self.on_dt_underflow = on_dt_underflow
        self.on_failure = on_failure
        self.on_growth = on_growth
        self.on_retire = on_retire
        self.max_rounds = max_rounds
        self.rounds = 0
        self.retired: list = []
        #: template member state for idle-lane padding (inert masked lanes)
        self._template = template if template is not None else members[0].state
        self.lanes: list = [None] * batch
        # seed the lanes: every lane starts on the template (idle), then the
        # queue fills as many as it can
        self.ens = runner.make_ensemble([self._template] * batch,
                                        [IDLE_T_FINAL] * batch)
        for spec in members:
            self.admit(spec)

    # ----------------------------------------------------------- lane churn

    def _emit(self, record: dict):
        if self.metrics is not None:
            self.metrics(record)

    def _rng_state(self, spec: MemberSpec):
        return spec.rng.dump_state() if spec.rng is not None else None

    def _start_member(self, lane: int, spec: MemberSpec):
        # snapshot-decoded states carry no flight-recorder ring (the wire
        # never does) — normalize to the lanes' armed/stripped structure
        spec.state = self.runner.system.ensure_flight(spec.state)
        if self.runner.di_enabled and spec.rng is None:
            raise ValueError(
                f"member {spec.member_id}: dynamic-instability members need "
                "a per-member SimRNG (SimRNG(seed).member(i)) — the device "
                "DI update draws from its distributed stream")
        self.ens = self.ens._replace(
            states=set_lane(self.ens.states, lane, spec.state),
            t_final=self.ens.t_final.at[lane].set(spec.t_final))
        if self.runner.di_enabled:
            # seat the member's RNG stream carry next to its state leaves
            self.ens = self.ens._replace(
                di_rng=self.ens.di_rng.at[lane].set(rng_carry(spec.rng)))
        self.lanes[lane] = _Lane(spec=spec, t=float(spec.state.time),
                                 dt=float(spec.state.dt))
        # admission latency (queue entry -> lane seat): the serving SLO
        # skelly-serve's /stats reports; `obs summarize` folds it into the
        # lane-occupancy table
        wait_s = (max(0.0, _time.perf_counter() - spec.enqueued_at)
                  if spec.enqueued_at is not None else 0.0)
        # skelly-scope lane churn: "admit" seats a member before the first
        # batched step, "backfill" refills a lane freed mid-drain (the
        # continuous-batching move; obs summarize reports occupancy)
        obs_tracer.emit("lane",
                        action="admit" if self.rounds == 0 else "backfill",
                        lane=lane, member=spec.member_id,
                        queue_wait_s=round(wait_s, 6))
        self._emit({"event": "start", "member": spec.member_id, "lane": lane,
                    "t": float(spec.state.time), "t_final": spec.t_final,
                    "queue_wait_s": round(wait_s, 6)})
        if self.write_initial_frames and self.writer is not None:
            self.writer(spec.member_id, spec.state,
                        rng_state=self._rng_state(spec))
        logger.info("ensemble start member=%s lane=%d t_final=%g",
                    spec.member_id, lane, spec.t_final)

    def _retire_member(self, lane: int, reason: str = "finished",
                       final_state=None, extra: Optional[dict] = None):
        ln = self.lanes[lane]
        extra = extra or {}
        if self.on_retire is not None:
            # the member's exact final state, before the lane is reused —
            # the snapshot skelly-serve resumes evicted tenants from
            # (``final_state`` lets `evict` reuse its own fetch instead of
            # gathering the lane twice)
            if final_state is None:
                final_state = lane_state(self.ens.states, lane)
            self.on_retire(ln.spec.member_id, final_state, reason,
                           rng_state=self._rng_state(ln.spec), **extra)
        obs_tracer.emit("lane", action="retire", lane=lane,
                        member=ln.spec.member_id, reason=reason,
                        steps=ln.steps, **extra)
        self._emit({"event": "retire" if reason == "finished" else reason,
                    "member": ln.spec.member_id, "lane": lane, "t": ln.t,
                    "steps": ln.steps, "frames": ln.frames, **extra})
        logger.info("ensemble retire member=%s lane=%d t=%.6g steps=%d (%s)",
                    ln.spec.member_id, lane, ln.t, ln.steps, reason)
        self.retired.append(ln.spec.member_id)
        if (self.writer is not None and hasattr(self.writer, "close_member")
                and reason != "growth"):
            # file-based writers free the handle as the lane frees — except
            # on a growth reseat, where the member is about to re-admit at
            # the next capacity rung and keeps streaming to the same file
            self.writer.close_member(ln.spec.member_id)
        self.lanes[lane] = None
        self.ens = self.ens._replace(
            t_final=self.ens.t_final.at[lane].set(IDLE_T_FINAL))
        if self.queue:
            self._start_member(lane, self.queue.popleft())

    # -------------------------------------------------- incremental service

    def admit(self, spec: MemberSpec):
        """Enqueue one member; seat it immediately when a lane is free.

        The incremental half of the continuous-batching API (skelly-serve's
        admission path): lanes keep their compiled program — seating is pure
        leaf substitution (`runner.set_lane`), so tenants join a running
        service without retracing. Returns the lane index when the member
        seated now, None when it queued behind occupied lanes."""
        if spec.enqueued_at is None:
            spec.enqueued_at = _time.perf_counter()
        self.queue.append(spec)
        seated = None
        for lane in range(self.batch):
            if not self.queue:
                break
            if self.lanes[lane] is None:
                nxt = self.queue.popleft()
                self._start_member(lane, nxt)
                if nxt is spec:
                    seated = lane
        return seated

    def evict(self, lane: int, reason: str = "evicted") -> SimState:
        """Free an occupied lane mid-service and return the member's CURRENT
        state — the exact resume point, possibly newer than its last
        dt_write frame. The lane backfills from the queue like any
        retirement (skelly-serve's graceful-eviction path)."""
        if not 0 <= lane < self.batch or self.lanes[lane] is None:
            raise ValueError(f"evict: lane {lane} is not occupied")
        state = lane_state(self.ens.states, lane)
        self._retire_member(lane, reason=reason, final_state=state)
        return state

    def lane_of(self, member_id: str):
        """Lane index currently running ``member_id``, or None."""
        for lane, ln in enumerate(self.lanes):
            if ln is not None and ln.spec.member_id == member_id:
                return lane
        return None

    def unqueue(self, member_id: str) -> Optional[MemberSpec]:
        """Drop a still-QUEUED member (never seated; no lane churn).
        Returns the removed spec — its ``state`` is the member's resume
        point (skelly-serve snapshots it) — or None when the id is not in
        the queue."""
        for spec in self.queue:
            if spec.member_id == member_id:
                self.queue.remove(spec)
                return spec
        return None

    @property
    def live(self) -> int:
        """Occupied lane count."""
        return sum(1 for ln in self.lanes if ln is not None)

    # ------------------------------------------------------------ the drain

    def run(self) -> list:
        """Drain queue + lanes to completion; returns retired member ids in
        retirement order."""
        while any(ln is not None for ln in self.lanes):
            if self.max_rounds is not None and self.rounds >= self.max_rounds:
                break
            self.poll()
        return self.retired

    def poll(self) -> list:
        """ONE batched round over the current lanes: step, record outcomes,
        write crossed frames, retire + backfill. A no-op on an idle (all
        lanes empty) scheduler. Returns the member ids retired this round.

        `run` is poll() in a loop; a long-lived service interleaves poll()
        with `admit`/`evict` between rounds — one compiled program
        throughout."""
        if not any(ln is not None for ln in self.lanes):
            return []
        p = self.runner.system.params
        retired_before = len(self.retired)
        live = sum(1 for ln in self.lanes if ln is not None)
        with obs_tracer.span("ensemble_step", round=self.rounds,
                             live=live, lanes=self.batch):
            wall0 = _time.perf_counter()
            self.ens, info = self.step_fn(self.ens)
            # ONE device fetch for all [B] outcome vectors (it doubles
            # as the span's device-work barrier)
            fetched = {f: np.asarray(getattr(info, f))
                       for f in ("running", "accepted", "iters",
                                 "residual", "residual_true",
                                 "fiber_error", "refines",
                                 "loss_of_accuracy", "dt_underflow",
                                 "dt_used", "t", "dt_next", "cycles",
                                 "health", "failed", "guard_retries",
                                 "nucleations", "catastrophes",
                                 "active_fibers", "needs_growth")}
            hist = (np.asarray(info.history)
                    if info.history is not None else None)
            # skelly-flight: the per-member recorder rings ride the stacked
            # state ([B, K, 13] + [B] counts) — one fetch serves the step
            # records, the failure payloads, and the telemetry events
            fl = self.ens.states.flight
            flight_rows = np.asarray(fl.rows) if fl is not None else None
            flight_counts = np.asarray(fl.count) if fl is not None else None
            wall_s = _time.perf_counter() - wall0
        self.rounds += 1
        if self.runner.di_enabled:
            # keep each seated member's SimRNG current with its in-trace
            # stream carry: frames/snapshots written below then resume with
            # the exact counters the device draws left off at
            counters = np.asarray(self.ens.di_rng)
            for lane, ln in enumerate(self.lanes):
                if ln is not None and ln.spec.rng is not None:
                    ln.spec.rng.distributed.counter = int(counters[lane, 2])

        for lane, ln in enumerate(self.lanes):
            if ln is None:
                continue
            if not bool(fetched["running"][lane]):
                # occupied but inert: the member was seated already at or
                # past its t_final (e.g. a degenerate swept t_final, or a
                # resumed state beyond it). Without this retire the lane
                # would spin the drain loop forever.
                self._retire_member(lane)
                continue
            accepted = bool(fetched["accepted"][lane])
            underflow = bool(fetched["dt_underflow"][lane])
            failed = bool(fetched["failed"][lane])
            health = int(fetched["health"][lane])
            dt_used = float(fetched["dt_used"][lane])
            t_new = float(fetched["t"][lane])
            flight_row = (flight_mod.last_row(flight_rows[lane],
                                              flight_counts[lane])
                          if flight_rows is not None else None)
            if bool(fetched["needs_growth"][lane]):
                # the member's nucleation burst outgrew this capacity
                # bucket: the runner froze the lane un-advanced (state and
                # RNG counter exactly as before the round). Hand the member
                # back for a reseat onto the next capacity rung — its
                # current state rides on_retire like any retirement
                # (scenarios.sweep and skelly-serve re-admit it there).
                cap = _fiber_capacity(lane_state(self.ens.states, lane))
                obs_tracer.emit("lane", action="growth", lane=lane,
                                member=ln.spec.member_id, capacity=cap,
                                t=ln.t)
                if self.on_growth == "raise":
                    raise RuntimeError(
                        f"ensemble member {ln.spec.member_id}: nucleation "
                        f"outgrew its fiber capacity bucket ({cap} slots) "
                        "at t="
                        f"{ln.t:.6g}; drive the sweep through scenarios."
                        "ScenarioEnsemble (or serve) for automatic growth "
                        "reseats, or start at a larger capacity")
                self._retire_member(lane, reason="growth",
                                    extra={"capacity": cap})
                continue
            if failed:
                # terminal health verdict: the runner froze the lane
                # un-advanced (quarantine — siblings bitwise-unaffected);
                # retire it as "failed" with the decoded verdict, or
                # mirror the sequential loop's abort. The flight
                # recorder's last-window tail + anomaly provenance ride
                # the failure record and the fault event (obs.flight —
                # "who and where" next to "something died").
                verdict_s = _verdict.describe(health)
                payload = (flight_mod.failure_payload(
                    flight_rows[lane], flight_counts[lane])
                    if flight_rows is not None else None)
                prov = (payload or {}).get("provenance") or {}
                prov_fields = ({"prov_field": prov.get("field"),
                                "prov_fiber": prov.get("fiber"),
                                "prov_node": prov.get("node")}
                               if prov else {})
                obs_tracer.emit("fault", kind="lane_failed", lane=lane,
                                member=ln.spec.member_id, health=health,
                                verdict=verdict_s, t=ln.t, **prov_fields)
                if self.on_failure == "raise":
                    raise RuntimeError(
                        f"ensemble member {ln.spec.member_id}: terminal "
                        f"solver health verdict '{verdict_s}' "
                        f"(health={health:#x}) at t={ln.t:.6g}")
                self._retire_member(lane, reason="failed",
                                    extra={"health": health,
                                           "verdict": verdict_s,
                                           "flight": payload})
                continue
            if underflow:
                # the sequential loop raises before writing this trial's
                # metrics line — no step record here either
                if self.on_dt_underflow == "raise":
                    raise RuntimeError(
                        f"ensemble member {ln.spec.member_id}: timestep "
                        f"smaller than dt_min ({p.dt_min}) at t={ln.t:.6g}"
                    )
                obs_tracer.emit("fault", kind="dt_underflow", lane=lane,
                                member=ln.spec.member_id, health=health,
                                t=ln.t)
                self._retire_member(lane, reason="dt_underflow",
                                    extra={"health": health,
                                           "verdict":
                                               _verdict.describe(health),
                                           "flight": (
                                               flight_mod.failure_payload(
                                                   flight_rows[lane],
                                                   flight_counts[lane])
                                               if flight_rows is not None
                                               else None)})
                continue
            ln.steps += 1
            self._emit({
                "event": "step", "member": ln.spec.member_id,
                "lane": lane, "round": self.rounds - 1,
                "step": ln.steps - 1, "t": ln.t,
                "dt": dt_used, "iters": int(fetched["iters"][lane]),
                "gmres_cycles": int(fetched["cycles"][lane]),
                "residual": float(fetched["residual"][lane]),
                "residual_true": float(fetched["residual_true"][lane]),
                "fiber_error": float(fetched["fiber_error"][lane]),
                "accepted": accepted,
                "refines": int(fetched["refines"][lane]),
                "loss_of_accuracy": bool(
                    fetched["loss_of_accuracy"][lane]),
                "health": health,
                "guard_retries": int(fetched["guard_retries"][lane]),
                "nucleations": int(fetched["nucleations"][lane]),
                "catastrophes": int(fetched["catastrophes"][lane]),
                "active_fibers": int(fetched["active_fibers"][lane]),
                "wall_s": round(wall_s, 4),
                "gmres_history": history_rows(
                    hist[lane] if hist is not None else None,
                    fetched["cycles"][lane]),
                "flight": flight_row})
            if flight_row is not None:
                # telemetry twin of the metrics column: `obs timeline`
                # renders these as per-member counter tracks
                obs_tracer.emit("flight", member=ln.spec.member_id,
                                lane=lane, **flight_row)
            ln.t = t_new
            ln.dt = float(fetched["dt_next"][lane])
            if (accepted and self.writer is not None
                    and crossed_write_boundary(t_new, dt_used,
                                               p.dt_write)):
                self.writer(ln.spec.member_id,
                            lane_state(self.ens.states, lane),
                            rng_state=self._rng_state(ln.spec))
                ln.frames += 1
            if reached_t_final(t_new, ln.spec.t_final):
                self._retire_member(lane)
        return self.retired[retired_before:]


def run_ensemble(system, members, batch: int = 8, *, batch_impl: str = "vmap",
                 writer=None, metrics=None, write_initial_frames: bool = False,
                 on_dt_underflow: str = "raise", on_failure: str = "raise",
                 max_rounds=None) -> list:
    """One-call convenience: build an `EnsembleRunner` over ``system`` and
    drain ``members`` (a MemberSpec iterable) through ``batch`` lanes."""
    runner = EnsembleRunner(system, batch_impl=batch_impl)
    return EnsembleScheduler(
        runner, members, batch, writer=writer, metrics=metrics,
        write_initial_frames=write_initial_frames,
        on_dt_underflow=on_dt_underflow, on_failure=on_failure,
        max_rounds=max_rounds).run()
