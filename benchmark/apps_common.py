"""What the adapters share."""

from __future__ import annotations


def expect(config: dict, facts: dict) -> None:
    """The deployment the configuration describes is the one the
    application realized: dtype, stencil radius, quantities and chips are
    stated in the file for the reader and checked here, never passed. The
    realized halo may be deeper than the stencil's radius (a deep-halo
    default is the application's choice), never shallower."""
    got = {"dtype": facts["dtype"], "quantities": facts["quantities"],
           "chips": facts["chips"]}
    want = dict(config.get("expects", {}))
    halo = max(max(r) for r in facts["radius_zyx"])
    bad = {k: (v, got.get(k)) for k, v in want.items()
           if k != "radius" and got.get(k) != v}
    if "radius" in want and halo < want["radius"]:
        bad["radius"] = (want["radius"], halo)
    if bad:
        raise RuntimeError(f"configuration expects {bad} (stated, realized)")


def max_abs_err(got, want) -> float:
    import numpy as np

    return float(np.max(np.abs(got.astype(np.float64)
                               - want.astype(np.float64))))


def reference_boxes(reference, seed: int, core, facts: dict, origins, dtype):
    """The reference's cores of the sampled boxes after the first chunk."""
    return [(o, reference.box_after(seed, o, core,
                                    facts["iters_per_dispatch"],
                                    facts["global_zyx"], dtype))
            for o in origins]


def lower_precision_sample(reference, seed: int, core, facts: dict, sample):
    """The control's sample: the reference computed in bfloat16 (state and
    arithmetic), to be put in the program's place."""
    import ml_dtypes

    return reference_boxes(reference, seed, core, facts,
                           [o for o, _ in sample], ml_dtypes.bfloat16)
