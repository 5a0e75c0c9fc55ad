"""Share of the window's host time spent replaying the device's record
stream and folding each lane's findings (``_replay``,
``_lane_findings``), in percent."""


def read(run):
    s = run.probes.seconds.get("replay", 0.0) \
        + run.probes.seconds.get("lane_findings", 0.0)
    if not s or run.window_s <= 0:
        return None
    return 100.0 * s / run.window_s
