"""Device time of the wavefront program per grid pass, from the trace's
program events, in ms."""


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.trace.module_seconds("wavefront_core")
    passes = run.probes.calls.get("grid", 0)
    if not calls or not passes:
        return None
    return 1e3 * seconds / passes
