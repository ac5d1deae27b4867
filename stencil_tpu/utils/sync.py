"""Device synchronization for timing.

JAX dispatch is asynchronous, so a timed region must end in a wait for the
device. ``hard_sync`` waits by fetching one scalar of the result, which
cannot arrive before everything queued ahead of it has run. It was written
for an earlier platform on which ``jax.block_until_ready`` could return
early; on the TPU v5e machine of PR 21 (jax 0.9.0, libtpu 0.0.34) the two
agree: one fused 12-step jacobi chunk at 512^3 took 18.0-18.2 ms under
``block_until_ready`` and 18.4-18.6 ms under ``hard_sync`` (medians of 7
in each of three ``chip_smoke.py`` runs), and a ``hard_sync`` issued right
after ``block_until_ready`` returned found nothing left to wait for
(3.0-3.4 ms, the cost of a stand-alone scalar fetch).

PR 54 measured it where the applications use it (``timed_chunk`` below,
read by ``benchmark/chunk_lib.py``; TPU v5e, two traced runs a cell; a
chunk's ``wait_s`` under ``hard_sync`` inside ``run()`` against the wait of
the benchmark's window under ``block_until_ready`` on the same compiled
loop, medians, ms): jacobi3d 512^3, ten iterations, 7.012 / 7.066 against
6.585 / 6.594; jacobi3d 768^3 21.432 / 21.527 against 21.085 / 21.045;
exchange 512^3 r3 q4, ten exchanges, 39.696 / 39.590 against 38.860 /
38.889; Astaroth 256^3, one iteration, 16.008 / 15.792 against 15.454 /
15.442, and its exchange-only chunk 2.669 / 2.544 for 0.16 ms of device
time (a third run of the first and the third cell, from the committed
files: 6.993 against 6.622 and 39.824 against 38.804). So the fetch (a
``dynamic_slice``, a ``squeeze`` and a 4-byte transfer after the loop's own
program) costs 0.35 to 1.02 ms a chunk over ``block_until_ready`` on one
chip, and the call itself (``enqueue_s``)
nothing: 0.198 to 0.277 ms on both sides. On four chips (the (1,2,2) jacobi,
(2,2,1) exchange, Astaroth and iso3dfd cells; one traced run each) a
chunk's wait reads 9.963, 76.280, 17.823 and 15.990 ms against the
window's 8.409, 74.748, 16.219 and 14.566: 1.42 to 1.60 ms more (the
slice of a sharded array is a program on every chip), while the window's
call costs 0.19 to 0.33 ms MORE than the chunk's (0.588 to 0.883 against
0.385 to 0.628: a traced window enqueues under the profiler, ``run()``
does not), so a chunk is 1.16 to 1.35 ms over a dispatch. Both waits are
correct there; ``block_until_ready`` is the cheaper one, and unlike the fetch it
waits for every shard of a sharded array, not only the device holding
element 0. Whether ``hard_sync`` stays is a later PR's choice (ROADMAP
C10): PR 54 measured it and changed no wait.

Either way, fuse iteration loops into one compiled program per chunk so a
chunk costs one dispatch and one sync."""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import numpy as np

# what a chunk's wait can be (the ``sync`` field of a chunk span): the fetch
# below, or a device scalar of the result that the loop's caller reads anyway
# (HPCG's ``normr``)
SYNCS = ("hard_sync", "scalar")


def hard_sync(tree) -> float:
    """Force completion of all queued work producing ``tree``; returns one
    element of the first leaf (cheap: a single-scalar transfer)."""
    leaf = jax.tree.leaves(tree)[0]
    idx = tuple(0 for _ in leaf.shape)
    return float(np.asarray(jax.device_get(leaf[idx])))


class ChunkMarks(NamedTuple):
    """What :func:`timed_chunk` noted of one chunk of a step loop: the
    fields a chunk span carries beside its name and its iterations
    (``obs/telemetry.Recorder.chunk_span``)."""

    t0_ns: int              # ``time.time_ns()`` as the chunk started
    enqueue_s: float        # from there until the compiled call returned
    wait_s: float           # from that return until the wait returned
    sync: str               # what the wait was: one of ``SYNCS``
    module: str             # the compiled loop's name (``scopes.MODULES``)
    value: float            # what the wait fetched

    @property
    def wall_s(self) -> float:
        return self.enqueue_s + self.wait_s


def timed_chunk(module: str, loop, *args, scalar=None):
    """Run one chunk the way every step loop of the applications does:
    note the time, call the compiled ``loop`` (the program ``module`` of
    ``scopes.MODULES``), note when the call returned (the host's part of a
    dispatch), wait for the device, note when the wait returned. Returns
    ``(loop(*args), ChunkMarks)``.

    The wait is :func:`hard_sync` on the result (its first leaf: the new
    state's, where a loop returns ``(curr, nxt)``) or, with ``scalar``,
    ``float(scalar(result))``: a device scalar the caller reads anyway."""
    t0_ns, t0 = time.time_ns(), time.perf_counter()
    out = loop(*args)
    t1 = time.perf_counter()
    value = hard_sync(out) if scalar is None else float(scalar(out))
    t2 = time.perf_counter()
    return out, ChunkMarks(t0_ns, t1 - t0, t2 - t1,
                           "hard_sync" if scalar is None else "scalar",
                           module, value)
