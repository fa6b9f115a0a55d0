"""Step phases: device self time a traced step of the ops under the
``advance`` scope — the fibers', shell's and bodies' state taken from the
solution (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("advance",))
