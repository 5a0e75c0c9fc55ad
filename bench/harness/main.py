"""One run of one cell: set-up, the measured window, the check, the line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up runs from process start to the first timed request: imports, the
chip, compiling or reading back every program the cell's traffic uses,
and the warm-up.  The window then runs ``--seconds``.  After it the peak
device memory is read, the program's state is dropped, and the plain
reference checks what the window produced.  With ``--trace 1`` the
window runs under the profiler and the line carries the per-layer
metrics and the trace's breakdown; with ``--trace 0`` the end-to-end
metrics.  The last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from harness.cell import BENCH, ROOT, Cell, load_cell, load_json, \
    metric_reader
from harness.probes import CompileClock, Probes

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoDevice(RuntimeError):
    """The run found no accelerator it may measure on."""


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the key), holding every program, however fast
    it compiled, so that only a checkout's first run of a cell
    compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_stamp(chips: int, require_tpu: bool = True) -> dict:
    """The device as JAX reports it, and its row of the peaks table.
    Without a TPU, or with fewer chips than the cell asks for, it
    raises: a number from another device is never reported."""
    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if require_tpu:
        if platform != "tpu":
            raise NoDevice(f"needs a TPU; JAX found {platform!r}")
        if len(devices) < chips:
            raise NoDevice(f"the cell asks for {chips} chips; JAX found "
                           f"{len(devices)}")
        peaks = load_json(BENCH / "peaks.json")["devices"]
        if kind not in peaks:
            raise NoDevice(f"device kind {kind!r} is not in "
                           "bench/peaks.json")
        peak = peaks[kind]
    else:
        peak = None
    return {"platform": platform, "kind": kind, "count": len(devices),
            "peak": peak, "devices": devices[:chips]}


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclass
class RunView:
    """What a metric reader in ``bench/metrics`` may read."""
    cell: Cell
    setup_s: float
    window_s: float
    probes: Probes
    clock: CompileClock
    peak: Optional[dict]
    lane_days: float = 0.0
    trace: object = None                  # trace_reduce.Reduced


# -- the probes --------------------------------------------------------------

def _count_detector(probes, args, kwargs, compiled):
    S, B, T, n = args[:4]
    probes.add("detector_seed_ticks", S * T)
    if compiled:
        probes.add("detector_compiled_seed_ticks", S * T)


def _pass1_shape(args, kwargs):
    """(backend, S, B, T, n) of one detector pass-1 call, unpadded."""
    Sp, B, Tp, n = args[0].shape
    S, T = kwargs.get("prepadded") or (Sp, Tp)
    return (kwargs.get("backend", "xla"), S, B, T, n)


def install_probes(probes: Probes) -> None:
    wf = "repro.kernels.wavefront.ops"
    probes.wrap("repro.core.batch", "run_findings_stacked", "engine")
    probes.wrap(wf, "run_findings_grid", "grid")
    probes.wrap(wf, "build_lane_tables", "tapes")
    probes.wrap(wf, "_run_core", "device_pass")
    probes.wrap(wf, "_replay", "replay")
    probes.wrap(wf, "_lane_findings", "lane_findings")
    probes.wrap("repro.control.streaming", "_worth_compiling",
                "detector_dispatch", on_result=_count_detector)
    probes.wrap("repro.kernels.robust_stats.ops", "hit_block",
                "detector_pass1", keep=_pass1_shape)


# -- the loops ----------------------------------------------------------------

def run_sweep(cell: Cell, args, window: "Window"):
    from harness import sweep
    run = sweep.build(cell, args.seed)
    cfgs = sweep.program_configs(run)
    sweep.warm(run, cfgs)
    t_window = time.perf_counter()
    with window:
        sweep.window(run, cfgs, args.seconds)
    passes = len(run.passes) - 1
    extra = {"lane_days": run.lane_days,
             "attempted": passes * len(run.specs) * len(run.seeds)}

    def verdict(control=None):
        return sweep.check(run, args.seed,
                           int(cell.traffic["check_lanes"]), control)
    return t_window, run.window_s, extra, verdict


LOOPS = {"sweep": run_sweep}


# -- the run -------------------------------------------------------------------

class Window:
    """Opens the window: counters and spans start from nought, compiles
    are counted, and the profiler runs where the run is traced."""

    def __init__(self, probes: Probes, clock: CompileClock, trace_dir):
        self.probes, self.clock, self.trace_dir = probes, clock, trace_dir

    def __enter__(self):
        import jax
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            from trace_reduce import start
            start(str(self.trace_dir))
        self.probes.reset()
        self.probes.recording = self.clock.open = True
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self.span.__exit__(*exc)
        self.probes.recording = self.clock.open = False
        if self.trace_dir is not None:
            jax.profiler.stop_trace()
        return False


def _number(v: float):
    """A compared number as JSON can hold it."""
    return v if math.isfinite(v) else str(v)


def checks_of(cell: Cell, verdict: dict) -> Dict[str, dict]:
    """Each number compared beside its limit (from the traffic file)."""
    limits = cell.traffic["limits"]
    return {k: {"value": verdict[k], "limit": limits[k]}
            for k in limits if k in verdict}


def passes(checks: Dict[str, dict]) -> bool:
    """``correct``: every compared number is finite and within its
    limit."""
    return len(checks) > 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


def execute(argv: List[str], t_start: float, *, require_tpu: bool = True,
            cell: Optional[Cell] = None, with_control: bool = False,
            out=None, err=None) -> int:
    """One run.  Tests pass ``require_tpu=False`` and a small ``cell``;
    ``bench/control.py`` passes ``with_control``, which also reads the
    control (the reference in the program's place, a precision lower)
    on the same requests and prints it under ``control``."""
    out, err = out or sys.stdout, err or sys.stderr
    args = parse(argv)
    cell = cell or load_cell(args.workload)
    use_cache()
    try:
        device = device_stamp(cell.chips, require_tpu)
    except NoDevice as e:
        print(f"bench: {e}", file=err)
        return 2
    loop = LOOPS[cell.traffic["loop"]]
    probes, clock = Probes(), CompileClock()
    install_probes(probes)
    where = TRACE_DIR / args.workload
    window = Window(probes, clock, where if args.trace else None)
    t_window, window_s, extra, verdict = loop(cell, args, window)
    setup_s = t_window - t_start
    mem = memory_peak(device["devices"])
    probes.unwrap()
    gc.collect()
    import jax
    jax.clear_caches()

    reduced = None
    if args.trace:
        from trace_reduce import find_xplane, reduce_trace
        path = find_xplane(str(where))
        reduced = reduce_trace(path) if path else None

    view = RunView(cell=cell, setup_s=setup_s, window_s=window_s,
                   probes=probes, clock=clock, peak=device["peak"],
                   lane_days=extra.get("lane_days", 0.0), trace=reduced)
    specs = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in specs:
        value = metric_reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    result = verdict()
    checks = checks_of(cell, result)
    correct = passes(checks)
    line = {
        "correct": bool(correct),
        "attempted": int(extra.get("attempted", 0)),
        "failed": int(extra.get("failed", 0)),
        "metrics": metrics,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"], "memory_peak_bytes": mem},
    }
    if args.trace and reduced is not None:
        line["device"]["busy_s"] = reduced.busy_s
        line["device"]["window_s"] = reduced.window_s
        line["breakdown"] = reduced.breakdown()
    for k, v in result.items():
        if k not in checks:
            print(f"bench: {k} = {v}", file=err)
    for k, c in checks.items():
        print(f"bench check: {k} = {c['value']!r} (limit {c['limit']!r})",
              file=err)
    if with_control:
        from harness.reference import control_precision
        ctl = checks_of(cell, verdict(control_precision(cell.config)))
        line["control"] = {k: _number(c["value"]) for k, c in ctl.items()}
        line["control_correct"] = passes(ctl)
    line["checks"] = {k: {"value": _number(c["value"]), "limit": c["limit"]}
                      for k, c in checks.items()}
    print(json.dumps(line, allow_nan=False, default=_jsonable), file=out,
          flush=True)
    return 0


def _jsonable(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    raise TypeError(f"not JSON: {v!r}")
