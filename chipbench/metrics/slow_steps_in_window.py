"""Host run loop: rows of the window whose step record the program marked
slow (``slow`` set: over twice the median of the records before it,
`skellysim_tpu/obs/step_record.py`); the expectation is 0, as
`compiles_in_window`'s. None against a program whose rows carry no record."""


def read(run):
    rows = [r for r in run.rows if "loop_s" in r]
    return float(sum(1 for r in rows if r.get("slow"))) if rows else None
