"""Host run loop: milliseconds a traced step in which the device sat idle
inside `System.run` (the ``skelly/run`` span); `run.probes["host_gaps"]`
splits the gaps by the run-loop span the host was in (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.host_gap_ms(run)
