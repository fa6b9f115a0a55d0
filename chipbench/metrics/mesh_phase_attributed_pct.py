"""Device: the share of the traced window's op time, over all device
planes, that the fold puts under a scope through the HLO metadata itself
and not by inference from neighbouring ops: what the per-chip seconds of a
mesh run cannot see is the rest. `phase_attributed_pct` of the four-chip
cell (`phases.py`: a share, so the sum over the planes serves)."""

import phases

probe = phases.probe


def read(run):
    return phases.attributed_pct(run)
