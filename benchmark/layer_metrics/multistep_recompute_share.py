"""Share of the rows one pass of the temporal multistep computes that a
pass with no recompute would not: 1 - ``rows_kept`` / ``rows_computed`` of
the program's ``kernel.multistep.staging`` record for the loop the window
dispatched (row strips recompute ``k - s`` rows each side at stage s, a
re-anchored last strip its overlap; full planes on one block read 0)."""

from benchmark import scope_lib

SHOWN = ("k", "rows", "strips", "halo_rows", "rows_computed", "rows_kept",
         "vmem_bytes")


def newest(records, module):
    """The newest staging record of ``module``, ``None`` where the program
    recorded none."""
    mine = [r for r in records if r.get("module") == module
            and r.get("rows_computed")]
    return mine[-1] if mine else None


def read(ctx):
    prog = scope_lib.program()
    module = scope_lib.module_name(ctx["trace"])
    if prog is None or module is None:
        return None
    staged = newest(prog[1].get().records(
        kind="counter", name="kernel.multistep.staging"), module)
    if staged is None:
        return None
    ctx["say"](f"multistep staging of {module}: " + ", ".join(
        f"{key}={staged.get(key)}" for key in SHOWN))
    return 100.0 * (1.0 - staged["rows_kept"] / staged["rows_computed"])
