"""Host run loop: median of the window's per-step `wall_s` as `System.run`
itself records it (host clock around the step's host fetch) — the steady
statistic beside the whole-window `step_wall_s`."""

import statistics


def read(run):
    walls = [r["wall_s"] for r in run.rows]
    return statistics.median(walls) if walls else None
