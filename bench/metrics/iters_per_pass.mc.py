"""Iterations of the device pass per grid pass (the program's
``grid.iterations``: the clean pass's ``while_loop`` trip count, which
the busiest lane sets).

The window does not snapshot ``repro.tracing``, so this reads the
record ``repro.kernels.wavefront.ops.last_grid_pass`` keeps of the last
grid call, which is the window's last pass; the cell's passes run the
same lanes.  A program without that record reads nothing."""


def read(run):
    if not run.probes.calls.get("grid"):
        return None
    try:
        from repro.kernels.wavefront import ops
    except ImportError:
        return None
    last = getattr(ops, "last_grid_pass", None)
    if not last or "iterations" not in last:
        return None
    return float(last["iterations"])
