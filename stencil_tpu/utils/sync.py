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
(3.0-3.4 ms, the cost of a stand-alone scalar fetch). Both are correct
there; ``block_until_ready`` is the cheaper one, and unlike the fetch it
waits for every shard of a sharded array, not only the device holding
element 0. Whether ``hard_sync`` stays is a later PR's choice.

Either way, fuse iteration loops into one compiled program per chunk so a
chunk costs one dispatch and one sync."""

from __future__ import annotations

import jax
import numpy as np


def hard_sync(tree) -> float:
    """Force completion of all queued work producing ``tree``; returns one
    element of the first leaf (cheap: a single-scalar transfer)."""
    leaf = jax.tree.leaves(tree)[0]
    idx = tuple(0 for _ in leaf.shape)
    return float(np.asarray(jax.device_get(leaf[idx])))
