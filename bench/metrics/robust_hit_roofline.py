"""Detector pass 1's share of its HBM roofline on the device, in percent:
the least bytes of every Pallas pass-1 call in the window (read the
metric block and the mask once, write the votes once) over the chip's
peak HBM bandwidth, divided by the device time of the jitted pass-1
program (``_hit_pallas``: pad, kernel, slice) in the trace.  HBM-bound
by choice: the chip publishes no peak for the vector unit that runs the
kernel's comparisons."""
from costs.robust_hit import least_bytes

PROGRAM = "_hit_pallas"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds, _ = run.trace.module_seconds(PROGRAM)
    calls = [c for c in run.probes.args.get("detector_pass1", [])
             if c[0] == "pallas"]
    if seconds <= 0 or not calls:
        return None
    least = sum(least_bytes(*c[1:]) for c in calls)
    return 100.0 * least / run.peak["hbm_bytes_per_s"] / seconds
