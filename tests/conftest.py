"""Test configuration: force an 8-device virtual CPU platform with float64.

Mirrors the reference's multi-rank-without-a-cluster strategy
(`/root/reference/tests/core/unit_tests/CMakeLists.txt:12-19`: ctest under
`mpiexec -n 2`): sharding correctness is exercised on a virtual device mesh, and
physics accuracy gates run in float64 on CPU. The pin happens here, before JAX
initializes any backend, so no test (and no xdist worker) ever takes a chip:
the run on the TPU is `python chip_smoke.py`, one process, outside pytest.
"""

from skellysim_tpu.utils.bootstrap import force_cpu_devices

force_cpu_devices(8)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", True)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled executables between test modules.

    A full-suite run compiles 250+ pjit programs into one process; with all
    of them held live, the XLA:CPU compiler segfaults nondeterministically
    around the ~85% mark (observed twice in round 5, inside
    backend_compile_and_load — the crash needs the accumulation: every
    individual module passes alone). Clearing per module caps the number of
    live executables; the recompiles it causes are per-module state anyway.
    """
    yield
    jax.clear_caches()
