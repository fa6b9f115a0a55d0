#!/usr/bin/env python3
"""What the run loop's step record costs a step (`obs/step_record.py`): the
loop's own span set, opened and closed around nothing, ``--cycles`` times
with the record (a collecting ``run`` span, `StepRecorder.enter` / `close` /
`leave` as `System.run(max_steps=1)` makes them: two reads of the counters a
step) and as many times without (the spans alone, which the loop carried
before there was a record). One JSON line: milliseconds a cycle both ways,
their difference (the record's cost a step) and one read of the counters
alone. On the chip's host:

    chiprun -- python scripts/step_record_cost.py

(kept in `chiprun_out/step_record_cost.json`). No device is touched.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def loop_spans(span):
    """One trip of `System._run_loop`'s spans with a frame written
    (docs/observability.md "Run-loop spans"), around nothing."""
    with span("clock_read"):
        pass
    with span("step", step=0):
        for name in ("dispatch", "wait", "fetch_info", "flight_row", "log",
                     "advance_clock"):
            with span(name):
                pass
        with span("write_frame", t=0.0):
            with span("encode"):
                pass
            with span("io", bytes=0):
                pass
        with span("clock_read"):
            pass
        with span("metrics_row"):
            pass


def measure(cycles: int) -> dict:
    from skellysim_tpu.obs import step_record
    from skellysim_tpu.obs.tracer import span

    def with_record(recorder):
        with span("run") as run_span:
            run_span.collect(recorder.span_closed)
            recorder.enter()
            loop_spans(span)
            step_record.row_fields(recorder.close(0))
            recorder.leave()

    def spans_alone(_):
        with span("run"):
            loop_spans(span)

    out = {"cycles": cycles}
    for name, trip in (("spans_ms", spans_alone), ("record_ms", with_record),
                       ("spans_again_ms", spans_alone)):
        recorder = step_record.StepRecorder()
        trip(recorder)          # the first span imports the annotation
        t0 = time.perf_counter()
        for _ in range(cycles):
            trip(recorder)
        out[name] = (time.perf_counter() - t0) / cycles * 1e3
    out["cost_ms_per_step"] = out["record_ms"] - min(out["spans_ms"],
                                                     out["spans_again_ms"])
    t0 = time.perf_counter()
    for _ in range(cycles):
        counters = step_record.read_counters()
    out["read_counters_ms"] = (time.perf_counter() - t0) / cycles * 1e3
    out["pressure_readable"] = {k: counters[k] is not None
                                for k in counters if k.startswith("psi_")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cycles", type=int, default=10000)
    args = ap.parse_args(argv)
    out = measure(args.cycles)
    line = json.dumps(out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step_record_cost.json"),
              "w") as fh:
        fh.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
