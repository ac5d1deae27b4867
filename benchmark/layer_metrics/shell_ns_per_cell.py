"""Device self time the overlap shells cost a shell cell: every op under
``stencil.sweep.shell`` (substep 0's multi-block-axis shells, re-integrated
in XLA from the exchanged halos), an iteration, mean over chips, over
``shell_cells`` of the program's ``astaroth.step_plan`` record for the loop
the window dispatched. Printed beside it: what the fused kernel pays a cell
and substep (``kernel_scope_ms_per_iter`` over 3 x ``block_cells``) for the
same equations. Nothing where the program recorded no plan or no shells."""

from benchmark import scope_lib

SHELL = "stencil.sweep.shell"
SHOWN = ("mode", "pallas", "tight_x", "blocks", "quantities",
         "exchanges_per_iter", "shells", "shell_cells", "block_cells",
         "halo_bytes_sent")
SUBSTEPS = 3


def newest(records, module):
    """The newest step plan of ``module`` that integrates shells, ``None``
    where the program recorded none."""
    mine = [r for r in records if r.get("module") == module
            and r.get("shell_cells")]
    return mine[-1] if mine else None


def read(ctx):
    prog = scope_lib.program()
    out = scope_lib.scoped(ctx)
    if prog is None or out is None:
        return None
    plan = newest(prog[1].get().records(
        kind="counter", name="astaroth.step_plan"), out["module"])
    if plan is None:
        return None
    chips = ctx["trace"]["chips"]
    shell_ns = sum(op["self"] for chip in chips for op in chip["ops"]
                   if op["scope"] == SHELL
                   ) / len(chips) / ctx["window"]["iterations"]
    value = shell_ns / plan["shell_cells"]
    kernel_ns = 1e6 * out["ms"]["kernel"] / (SUBSTEPS * plan["block_cells"])
    say = ctx["say"]
    say(f"step plan of {out['module']}: " + ", ".join(
        f"{key}={plan.get(key)}" for key in SHOWN))
    say(f"shells: {shell_ns / 1e6:.4f} ms an iteration under {SHELL} over "
        f"{plan['shell_cells']} cells = {value:.4f} ns a cell; the fused "
        f"kernel pays {kernel_ns:.4f} ns a cell and substep")
    return value
