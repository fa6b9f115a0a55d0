"""Device time by step phase and operator, and the host's part of every
idle gap: what the readers of the `*_device_s` metrics share.

`xplane.py` says a TPU trace carries "NO scope path". That holds for the op
EVENTS (an event is named by its HLO text and has three timing stats); the
same ``.xplane.pb`` holds every module's optimized HLO on its
``/host:metadata`` plane, with the `jax.named_scope` path of each
instruction, and the program's own fold (`skellysim_tpu.obs.profile
.load_device_trace`) joins the two. This helper calls that fold on the
window's dump, clipped to the traced window, once a run; it computes
nothing of its own, so the benchmark and `python -m skellysim_tpu.obs
profile DIR` read one number.

Importing this module puts the scope paths into the compile-cache key
(`include_scopes_in_cache_key`): JAX leaves them out, so a cache entry
compiled before a scope was added would hide it from the fold. Readers are
loaded before the build, and per-layer readers only with ``--trace 1``:
untraced runs keep the keys they had.

Against a program that lacks the fold (the parent of the PR that added
these metrics) every reader returns None and the line leaves the metric
out. It does so too where the fold reports stale metadata, and where no
path holds the scope a metric reads: never a zero for "not seen".
"""

from __future__ import annotations

import glob
import os
import tempfile

try:
    from skellysim_tpu.obs import profile as _profile

    _profile.include_scopes_in_cache_key()
except (ImportError, AttributeError):
    _profile = None     # a program from before the fold: read nothing

#: idle gaps shorter than this are left out of the gap table
MIN_GAP_US = 100.0


def _window_dump():
    """The newest window trace a harness of this user wrote under the
    temporary directory (`run.py` keeps it in ``<work>/trace`` until the
    run's end and does not hand the path over)."""
    hits = glob.glob(os.path.join(tempfile.gettempdir(), "chipbench_*",
                                  "trace", "plugins", "profile", "*",
                                  "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


def probe(run) -> None:
    """Fold the window's dump once; every reader's `probe` is this one."""
    if _profile is None or "phases" in run.probes or run.trace is None:
        return
    path = getattr(run, "trace_path", None) or _window_dump()
    if path is None:
        return
    fold = _profile.load_device_trace(path, window=run.trace.window_ns)
    run.phase_fold = fold
    run.probes["phases"] = {
        phase: {op: round(s, 6) for op, s in row.items()}
        for phase, row in fold.cross_table().items()}
    run.probes["host_gaps"] = [
        [g["label"], g["count"], round(g["ms"], 3)]
        for g in fold.gap_table(MIN_GAP_US)]
    run.probes["phase_fold"] = {
        "op_self_s": fold.total_us * 1e-6, "busy_s": fold.busy_us * 1e-6,
        "inferred_s": fold.inferred_us * 1e-6,
        "stale_metadata": fold.stale}


def _fold(run):
    fold = getattr(run, "phase_fold", None)
    return None if fold is None or fold.stale else fold


def seconds(run, has=(), lacks=()):
    """Device self time, in seconds a traced step, of the ops whose scope
    path holds every component of ``has`` and none of ``lacks``; None where
    no op's path does."""
    fold = _fold(run)
    if fold is None:
        return None
    total = fold.seconds(has, lacks)
    if total is None:
        return None
    return total / max(len(run.trace.span_seconds("chipbench_step")), 1)


def attributed_pct(run):
    """Op time attributed through the HLO metadata itself (not inferred
    from neighbours), as a share of all op time in the traced window."""
    fold = _fold(run)
    if fold is None or fold.total_us <= 0:
        return None
    return 100.0 * (fold.attributed_us - fold.inferred_us) / fold.total_us


def host_gap_ms(run):
    """Milliseconds a step in which the device sat idle inside the run
    loop's own ``skelly/run`` span."""
    fold = _fold(run)
    if fold is None:
        return None
    runs = [s for s in fold.spans if s[2] == "skelly/run"]
    if not runs:
        return None
    return fold.idle_us_inside("skelly/run") * 1e-3 / len(runs)
