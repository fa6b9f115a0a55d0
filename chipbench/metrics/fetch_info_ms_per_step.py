"""Host run loop: milliseconds a step in the ``fetch_info`` span (the scalar
fetches of `StepInfo` after the first, one device-to-host copy each) —
median over EVERY row of the window of the step record's ``host_ms``. None
against a program whose rows carry no record."""

import statistics


def read(run):
    ms = [r["host_ms"]["fetch_info"] for r in run.rows
          if "loop_s" in r and "fetch_info" in r["host_ms"]]
    return statistics.median(ms) if ms else None
