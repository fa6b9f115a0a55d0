"""Host run loop: milliseconds a frame — mean of the step record's
``write_frame/encode`` (the state fetched and packed) plus ``write_frame/io``
(written and flushed) over the window's rows that wrote a frame. None where
the window wrote none, or the rows carry no record."""


def read(run):
    ms = [r["host_ms"]["write_frame/encode"]
          + r["host_ms"].get("write_frame/io", 0.0) for r in run.rows
          if "loop_s" in r and "write_frame/encode" in r["host_ms"]]
    return sum(ms) / len(ms) if ms else None
