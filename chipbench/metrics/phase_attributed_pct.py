"""Device: the share of the traced window's op time that the fold puts under
a scope through the HLO metadata itself, not by inference from neighbouring
ops (`phases.py`). What the `*_device_s` metrics cannot see is the rest."""

import phases

probe = phases.probe


def read(run):
    return phases.attributed_pct(run)
