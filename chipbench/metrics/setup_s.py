"""End to end: process start to the window's start — imports, scene and
precompute (a cache miss pays it here), build, operator load, the compiled
step and one further step."""


def read(run):
    return run.setup_s
