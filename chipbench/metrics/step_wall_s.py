"""End to end: the whole window's wall time over all trial steps taken in
it — frame encode and write, host fetches, the harness's own hold of the
state and any stall included. Host clock."""


def read(run):
    return run.window_wall_s / run.n_steps if run.n_steps else None
