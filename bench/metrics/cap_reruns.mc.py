"""Device passes rerun because a lane reached a capacity, per grid pass
(``_run_core`` calls per ``run_findings_grid`` call, less one)."""


def read(run):
    grids = run.probes.calls.get("grid", 0)
    if not grids:
        return None
    return run.probes.calls.get("device_pass", 0) / grids - 1.0
