"""Host run loop: milliseconds a traced step in which the devices sat idle
inside `System.run` (the ``skelly/run`` span) on a mesh run;
`run.probes["host_gaps"]` splits the gaps by the run-loop span the host was
in, ``run/place_state`` among them. `host_gap_ms_per_step` of the
four-chip cell (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.host_gap_ms(run)
