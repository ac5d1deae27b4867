"""Multi-process initialization and colocation discovery.

The TPU-native analogue of the reference's MPI bootstrap: where the
reference discovers colocated ranks with ``MPI_Comm_split_type(SHARED)``
(reference: mpi_topology.hpp:20-30) and launches via mpiexec/jsrun
(reference: README.md:131-168, scripts/summit/*.sh), a JAX multi-host run
calls :func:`init_distributed` in every process before any device access.
After it returns, ``jax.devices()`` is the *global* device list and the
whole stack — NodePartition's host-level outer split (api.realize),
process-grouped placement (placement.IntraNodeRandom), cross-process
``ppermute``s in the exchange — operates over all hosts; XLA routes the
collectives over ICI within a slice and the data-centre network (Gloo on
CPU) across hosts.

Launch styles:
- TPU pods / GKE: ``init_distributed()`` with no arguments — JAX picks up
  the cluster environment automatically.
- Manual / CPU simulation (the reference's "2 ranks on one node" idiom,
  test/CMakeLists.txt:49): pass ``coordinator``/``num_processes``/
  ``process_id`` explicitly or via ``STENCIL_COORDINATOR``,
  ``STENCIL_NUM_PROCESSES``, ``STENCIL_PROCESS_ID`` env vars;
  ``local_cpu_devices=N`` gives each process N virtual CPU devices
  (collectives ride Gloo). Exercised by tests/test_multiprocess.py.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence


def init_distributed(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_cpu_devices: int = 0,
):
    """Initialize JAX's distributed runtime (call before any device use).

    Returns ``(process_index, process_count)``. All arguments fall back to
    the ``STENCIL_COORDINATOR`` / ``STENCIL_NUM_PROCESSES`` /
    ``STENCIL_PROCESS_ID`` environment variables; with none set, JAX's
    automatic cluster detection is used (TPU pod slices).
    """
    import jax

    coordinator = coordinator or os.environ.get("STENCIL_COORDINATOR")
    if num_processes is None and os.environ.get("STENCIL_NUM_PROCESSES"):
        num_processes = int(os.environ["STENCIL_NUM_PROCESSES"])
    if process_id is None and os.environ.get("STENCIL_PROCESS_ID"):
        process_id = int(os.environ["STENCIL_PROCESS_ID"])

    if local_cpu_devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", int(local_cpu_devices))

    if coordinator is None:
        jax.distributed.initialize()
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    return jax.process_index(), jax.process_count()


def maybe_init_from_env() -> bool:
    """Initialize the distributed runtime iff the STENCIL_* launch env is
    present (set by scripts/launch_multiprocess.sh or a cluster launcher);
    no-op otherwise. Returns whether initialization happened. Apps call
    this at the top of ``main()`` so the same CLI works single- and
    multi-process."""
    if not os.environ.get("STENCIL_COORDINATOR"):
        return False
    init_distributed(
        local_cpu_devices=int(os.environ.get("STENCIL_LOCAL_CPU_DEVICES", "0"))
    )
    return True


def colocated_devices(devices: Optional[Sequence] = None) -> Dict[int, List]:
    """Devices grouped by owning process — the ``MpiTopology.colocated``
    analogue (reference: mpi_topology.hpp:95)."""
    import jax

    devices = list(devices) if devices is not None else jax.devices()
    groups: Dict[int, List] = {}
    for d in devices:
        groups.setdefault(d.process_index, []).append(d)
    return groups


def local_devices(devices: Optional[Sequence] = None) -> List:
    """This process's own devices (the reference's per-rank GPU set,
    src/stencil.cu:74-85)."""
    import jax

    return colocated_devices(devices).get(jax.process_index(), [])
