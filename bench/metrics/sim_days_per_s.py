"""Simulated campaign-days (lanes x days) completed over all the time of
the window; the window ends with its last pass."""


def read(run):
    if not run.lane_days or run.window_s <= 0:
        return None
    return run.lane_days / run.window_s
