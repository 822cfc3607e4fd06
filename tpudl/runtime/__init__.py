"""L0 runtime: device/mesh discovery and distributed bring-up."""

from tpudl.runtime.compile_cache import enable_compile_cache  # noqa: F401
from tpudl.runtime.distributor import TpuDistributor  # noqa: F401
from tpudl.runtime.mesh import (  # noqa: F401
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_SEQ,
    AXIS_TENSOR,
    MESH_AXES,
    MeshSpec,
    batch_partition_spec,
    make_mesh,
    window_partition_spec,
)
from tpudl.runtime.rng import use_hardware_rng  # noqa: F401

# Place the persistent compile cache at import — before the first jit
# compiles — so every entrypoint that touches the runtime gets it
# without its own plumbing (JAX_COMPILATION_CACHE_DIR moves it).
enable_compile_cache()
