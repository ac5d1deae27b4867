from .mesh import (
    AXIS_X, AXIS_Y, AXIS_Z, BLOCK_PSPEC, MESH_AXES, block_sharding,
    grid_mesh, mesh_dim,
)
from .exchange import Method, HaloExchange, direction_bytes
from .placement import (
    FixedAssignment, IntraNodeRandom, NodeAware, Placement, Trivial,
    comm_matrix,
)
from .topology import Boundary, Topology, link_cost_matrix

__all__ = [
    "AXIS_X",
    "AXIS_Y",
    "AXIS_Z",
    "BLOCK_PSPEC",
    "Boundary",
    "FixedAssignment",
    "HaloExchange",
    "IntraNodeRandom",
    "MESH_AXES",
    "Method",
    "NodeAware",
    "Placement",
    "Topology",
    "Trivial",
    "block_sharding",
    "comm_matrix",
    "direction_bytes",
    "grid_mesh",
    "link_cost_matrix",
    "mesh_dim",
]
