"""Host run loop: the window's longest step record over its median
(``max(loop_s) / median(loop_s)``): near 1 in a steady window, whatever the
cell's step costs, and 6-10 where one step stalled. None against a program
whose rows carry no record."""

import statistics


def read(run):
    loops = [r["loop_s"] for r in run.rows if "loop_s" in r]
    return max(loops) / statistics.median(loops) if loops else None
