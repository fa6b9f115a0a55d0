"""Host run loop: the serial host part of a step, in milliseconds — median
over EVERY row of the window of the step record's ``loop_s`` less its
``dispatch`` and ``wait`` (`skellysim_tpu/obs/step_record.py`: what the loop
spent outside the enqueue and the wait on the device). None, and the line
leaves it out, against a program whose rows carry no record."""

import statistics


def read(run):
    rows = [r for r in run.rows if "loop_s" in r]
    if not rows:
        return None
    return statistics.median(
        r["loop_s"] * 1e3 - r["host_ms"].get("dispatch", 0.0)
        - r["host_ms"].get("wait", 0.0) for r in rows)
