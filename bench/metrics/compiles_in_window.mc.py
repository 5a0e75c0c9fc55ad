"""Programs compiled, or read back from the persistent cache, inside the
window (a compile-event listener); set-up should leave none."""


def read(run):
    return run.clock.compiles + run.clock.cache_loads
