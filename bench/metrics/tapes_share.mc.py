"""Share of the window's host time spent building the wavefront's lane
tables (``build_lane_tables``: rng tapes, event tables), in percent."""


def read(run):
    s = run.probes.seconds.get("tapes")
    if not s or run.window_s <= 0:
        return None
    return 100.0 * s / run.window_s
