"""stencil_tpu — TPU-native distributed 3D stencil halo-exchange framework.

A from-scratch JAX/XLA re-design with the capabilities of the reference
MPI/CUDA library socal-ucr/stencil (see SURVEY.md): multi-quantity 3D
domains, per-direction asymmetric radii, communication-minimizing
partitioning, 26-neighbor periodic halo exchange as ``shard_map``-ped
``lax.ppermute`` collectives over a 3D device mesh, and interior/exterior
comm/compute overlap inside a single jitted step.
"""

from .domain import DataHandle, GridSpec, LocalBlock
from .geometry import Dim3, Radius, Rect3
from .parallel import HaloExchange, Method, grid_mesh

__version__ = "0.1.0"

__all__ = [
    "DataHandle",
    "Dim3",
    "GridSpec",
    "HaloExchange",
    "LocalBlock",
    "Method",
    "Radius",
    "Rect3",
    "grid_mesh",
    "__version__",
]
