"""Session gang masks fetched from the device per grid pass, in MB (the
program's ``grid.gang_mask_bytes``, every device pass of the call).

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
    if not last or "gang_mask_bytes" not in last:
        return None
    return last["gang_mask_bytes"] / 1e6
