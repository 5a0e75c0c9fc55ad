"""Share of the window in which no operation ran on the device, from the
profiler's trace, in percent."""


def read(run):
    if run.trace is None or run.trace.n_devices == 0:
        return None
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
