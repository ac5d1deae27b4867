#!/usr/bin/env bash
# Launch an N-process run of a stencil_tpu app on ONE machine, each process
# with its own virtual CPU devices — the no-cluster multi-host idiom
# (reference launch scripts: scripts/summit/*.sh via jsrun, README.md:131-168;
# here jax.distributed over Gloo replaces mpiexec).
#
# Usage:
#   scripts/launch_multiprocess.sh <nprocs> <devices-per-proc> <module> [args...]
# Example (2 hosts x 4 devices, jacobi3d):
#   scripts/launch_multiprocess.sh 2 4 stencil_tpu.apps.jacobi3d --x 64 --iters 3
#
# CPU/Gloo ONLY, by construction: STENCIL_LOCAL_CPU_DEVICES makes every
# process force jax_platforms=cpu (parallel/distributed.init_distributed), so
# none of the N processes ever takes a chip — a chip belongs to one process
# at a time, and this script must never be how several reach for one.
#
# On a real TPU pod slice none of this is needed: every host runs the same
# command and `stencil_tpu.parallel.distributed.init_distributed()` picks up
# the cluster automatically.
set -euo pipefail
NPROCS=${1:?nprocs}
LOCAL=${2:?devices per process}
MODULE=${3:?python module}
shift 3
PORT=${STENCIL_PORT:-$((20000 + RANDOM % 20000))}

pids=()
for ((rank = 0; rank < NPROCS; rank++)); do
  STENCIL_COORDINATOR="localhost:${PORT}" \
  STENCIL_NUM_PROCESSES="${NPROCS}" \
  STENCIL_PROCESS_ID="${rank}" \
  STENCIL_LOCAL_CPU_DEVICES="${LOCAL}" \
  python -m "${MODULE}" "$@" &
  pids+=($!)
done
rc=0
for pid in "${pids[@]}"; do
  wait "${pid}" || rc=$?
done
exit "${rc}"
