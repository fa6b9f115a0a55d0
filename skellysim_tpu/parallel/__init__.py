from .mesh import (FIBER_AXIS, MEMBER_AXIS, make_mesh,  # noqa: F401
                   make_member_mesh, shard_ensemble, shard_state)
from .multihost import initialize as initialize_multihost  # noqa: F401
from .multihost import process_info  # noqa: F401
from .ring import (ring_oseen_contract, ring_stokeslet,  # noqa: F401
                   ring_stresslet)
from .spmd import (SpmdSolution, build_spmd_step,  # noqa: F401
                   spmd_shell_mode, spmd_step)
