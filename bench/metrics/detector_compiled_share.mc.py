"""Share of detector pass 1's seed-ticks that ran on the compiled
backend rather than the numpy floor, in percent."""


def read(run):
    total = run.probes.calls.get("detector_seed_ticks", 0)
    if not total:
        return None
    return 100.0 * run.probes.calls.get(
        "detector_compiled_seed_ticks", 0) / total
