"""bagua_tpu: a TPU-native distributed training acceleration framework.

A from-scratch JAX/XLA/Pallas/pjit redesign with the capabilities of
BaguaSys/bagua (see SURVEY.md): pluggable data-parallel relaxation algorithms
(centralized/decentralized x full/low precision x sync/async + QAdam) over a
bucketed communication layer on a hierarchical ``(inter, intra)`` device mesh,
plus autotuning, fused optimizer, MoE expert parallelism, checkpointing, and
an elastic launcher.
"""

import time as _time

import jax as _jax  # noqa: F401  whoever imports it first pays for it: not this package's body

_began = _time.perf_counter()

from bagua_tpu.observability.cold_start import cold_host_span as _cold_host_span  # noqa: E402

# the package's body is the process's first cold event (``bagua_host/setup/import``),
# counted from the top: importing the span itself is part of it
with _cold_host_span("setup", "import", detail=__name__, began=_began):
    from bagua_tpu.version import __version__  # noqa: F401
    from bagua_tpu.defs import ReduceOp  # noqa: F401
    from bagua_tpu.mesh import MeshSpec  # noqa: F401
    from bagua_tpu.communication import (  # noqa: F401
        BaguaProcessGroup,
        init_process_group,
        is_initialized,
        get_default_group,
        new_group,
        allreduce,
        allgather,
        reducescatter,
        broadcast,
        alltoall,
        reduce,
        scatter,
        gather,
        barrier,
        broadcast_object,
        local_ranks,
    )
    from bagua_tpu.env import (  # noqa: F401
        get_rank,
        get_world_size,
        get_local_rank,
        get_local_size,
    )
