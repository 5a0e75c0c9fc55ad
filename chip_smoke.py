#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip, in one process.

    python chip_smoke.py

Three phases, in order, each printing one line (backend, lanes, compile
seconds, wall seconds, parity verdict):

a. served what-if queries: the HTTP front door, in-process on a thread,
   answers four concurrent control-free presets of the paper campaign
   (63 nodes, 60-node gang, 73 days) at 256 seeds each.  They coalesce
   into one 1024-lane compiled wavefront pass; 8 seeds of each preset
   must equal the numpy engine bit for bit, and a repeated query must
   answer from the cache.  The same sampled lanes then run once through
   the wavefront's Pallas backend and must equal numpy too;
b. detector pass 1 on the device: the proactive preset runs with the
   streaming detector on the ``xla`` and then the ``pallas`` backend;
   both alarm sets must equal the numpy oracle's;
c. the trainer's recovery path: ``stablelm-3b`` at full width, depth cut
   to fit one chip, trains 12 steps with an injected XID 94 and resumes
   from the last checkpoint.

The last line of standard output is one JSON object naming the device.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PRESETS = ("paper-faithful", "flaky-fabric", "storage-degraded",
           "no-auto-retry")
SEEDS = 256                 # per served query: 4 x 256 = 1024 lanes
PARITY_SEEDS = 8            # per preset, checked against numpy
DETECTOR_SEEDS = 4
DETECTOR_DAYS = 2.0
TRAIN_LAYERS = 8            # of stablelm-3b's 32; see phase_train
TRAIN_STEPS = 12
TRAIN_BATCH, TRAIN_SEQ = 2, 128
FAIL_AT = 7                 # checkpoints land every 5 steps: resume at 5


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Seconds the backend spent compiling while the clock was open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == self.EVENT:
            self.seconds += secs

    @contextlib.contextmanager
    def phase(self):
        start, t0 = self.seconds, time.perf_counter()
        span = {}
        yield span
        span["compile_s"] = self.seconds - start
        span["wall_s"] = time.perf_counter() - t0


@contextlib.contextmanager
def counted(module, name: str, calls: list):
    """Record the arguments of every call of ``module.name``."""
    orig = getattr(module, name)

    def wrapper(*args, **kwargs):
        calls.append((args, kwargs))
        return orig(*args, **kwargs)
    setattr(module, name, wrapper)
    try:
        yield calls
    finally:
        setattr(module, name, orig)


def report(phase: str, span: dict, **fields) -> None:
    parts = [f"compile_s={span['compile_s']:.3f}",
             f"wall_s={span['wall_s']:.3f}"]
    parts += [f"{k}={v}" for k, v in fields.items()]
    print(f"[{phase}] " + " ".join(parts), flush=True)


# -- (a) served what-if queries ----------------------------------------------

def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read())


def phase_served(clock: CompileClock) -> None:
    import repro.kernels.wavefront.ops as wf_ops
    from repro.core.batch import BatchedCampaignEngine, run_findings_stacked
    from repro.ops import get_scenario
    from repro.ops.sweep import findings_distribution
    from repro.serve.http import make_server
    from repro.serve.service import ServiceConfig, WhatIfService

    passes = []                       # what the engine hook computed

    def engine(cfgs, seed_list):
        out = run_findings_stacked(cfgs, seed_list)
        passes.append(list(zip(cfgs, out)))
        return out

    # a wide window and an early dispatch at four requests: the four
    # concurrent POSTs share one window however the host schedules them
    svc = WhatIfService(ServiceConfig(window_s=1.0, max_batch=len(PRESETS)),
                        engine_fn=engine)
    server = make_server(svc)
    serving = threading.Thread(target=server.serve_forever, daemon=True)
    serving.start()
    url = "http://%s:%d/whatif" % server.server_address[:2]
    grid_calls: list = []
    try:
        with clock.phase() as span, \
                counted(wf_ops, "run_findings_grid", grid_calls):
            answers = [None] * len(PRESETS)
            start = threading.Barrier(len(PRESETS))

            def client(i):
                start.wait()
                answers[i] = _post(url, {"preset": PRESETS[i],
                                         "seeds": SEEDS})
            clients = [threading.Thread(target=client, args=(i,))
                       for i in range(len(PRESETS))]
            for c in clients:
                c.start()
            for c in clients:
                c.join()
        repeat = _post(url, {"preset": PRESETS[0], "seeds": SEEDS})
    finally:
        server.shutdown()
        server.server_close()
        svc.close()

    check(all(a is not None for a in answers), "every query answered")
    check([a["source"] for a in answers] == ["engine"] * len(PRESETS),
          "first answers come from the engine")
    check(len(passes) == 1, f"one coalesced engine pass (got {len(passes)})")
    check(len(grid_calls) == 1,
          f"one compiled grid pass (got {len(grid_calls)})")
    (grid_cfgs, grid_seeds), grid_kw = grid_calls[0]
    lanes = len(grid_cfgs) * len(grid_seeds)
    backend = grid_kw.get("backend")
    check(lanes >= len(PRESETS) * SEEDS and backend in ("xla", "pallas"),
          f"the device pass covers every lane (lanes={lanes}, "
          f"backend={backend})")
    check(repeat["source"] == "cache" and len(passes) == 1,
          "the repeated query answers from the cache")

    # the served distributions are the ones the device findings give,
    # and sampled lanes equal the numpy engine bit for bit
    by_name = {a["scenario"]: a for a in answers}
    check(sorted(by_name) == sorted(PRESETS), "one answer per preset")
    sample = list(range(PARITY_SEEDS))
    mismatched = []
    refs = {}
    for name in PRESETS:
        cfg = get_scenario(name).to_campaign_config(0)
        found = [f for c, f in passes[0] if c == cfg]
        check(len(found) == 1, f"{name}: one engine result")
        dist = json.loads(json.dumps(findings_distribution(
            list(found[0].values()))))
        check(by_name[name]["distribution"] == dist,
              f"{name}: served distribution = device findings")
        ref = BatchedCampaignEngine(
            cfg, wavefront_backend="numpy").run_findings(sample)
        for s in sample:
            if found[0][s] != ref[s]:
                mismatched.append((name, s, sorted(
                    k for k in ref[s] if ref[s][k] != found[0][s][k])))
        refs[name] = ref
    check(not mismatched, f"bitwise parity with numpy: {mismatched}")
    report("a served", span, backend=backend, lanes=lanes,
           queries=len(PRESETS), coalesced_passes=len(passes),
           repeat_source=repeat["source"],
           parity=f"bitwise {len(PRESETS) * len(sample)}/"
                  f"{len(PRESETS) * len(sample)} lanes")
    return refs


def phase_wavefront_pallas(clock: CompileClock, refs: dict) -> None:
    """The sampled lanes of (a) once more through the wavefront's Pallas
    gang-select backend, which the served path does not pick."""
    from repro.kernels.wavefront.ops import run_findings_grid
    from repro.ops import get_scenario

    sample = list(range(PARITY_SEEDS))
    cfgs = [get_scenario(name).to_campaign_config(0) for name in PRESETS]
    with clock.phase() as span:
        got = run_findings_grid(cfgs, sample, backend="pallas")
    mismatched = [(name, s) for name, g in zip(PRESETS, got)
                  for s in sample if g[s] != refs[name][s]]
    check(not mismatched, f"pallas: bitwise parity with numpy: {mismatched}")
    report("a wavefront/pallas", span, backend="pallas",
           lanes=len(cfgs) * len(sample),
           parity=f"bitwise {len(cfgs) * len(sample)}/"
                  f"{len(cfgs) * len(sample)} lanes")


# -- (b) detector pass 1 on the device ---------------------------------------

def phase_detector(clock: CompileClock) -> None:
    import repro.kernels.robust_stats.ops as rs_ops
    from repro.core.batch import BatchedCampaignEngine
    from repro.ops import get_scenario

    def alarms(backend):
        sc = get_scenario("proactive").replace(duration_days=DETECTOR_DAYS,
                                               detector_backend=backend)
        runs = BatchedCampaignEngine(sc.to_campaign_config(0)).run(
            list(range(DETECTOR_SEEDS)))
        return [r.control.alarms for r in runs]

    ref = alarms("numpy")
    check(sum(map(len, ref)) > 0, "the numpy oracle raises alarms")
    for backend in ("xla", "pallas"):
        calls: list = []
        with clock.phase() as span, counted(rs_ops, "hit_block", calls):
            got = alarms(backend)
        compiled = [kw for _, kw in calls if kw.get("backend") == backend]
        check(len(compiled) > 0,
              f"{backend}: spans reach the compiled pass "
              f"(COMPILED_MIN_ELEMS={rs_ops.COMPILED_MIN_ELEMS})")
        check(got == ref, f"{backend}: alarm sets equal numpy's")
        report(f"b detector/{backend}", span, backend=backend,
               lanes=DETECTOR_SEEDS, compiled_spans=len(compiled),
               days=DETECTOR_DAYS,
               alarms=sum(map(len, got)), parity="identical alarm sets")


# -- (c) the trainer's recovery path -----------------------------------------

def phase_train(clock: CompileClock) -> None:
    """stablelm-3b at its published widths.  Weights, gradients and AdamW
    moments take about 12 bytes a parameter; at 8 layers the step's
    program needs about 10.6 GiB of the chip's 16 GB (its compile for a
    v5e reports 8.3 GiB of donated state and 2.2 GiB of temporaries), at
    12 it would leave almost none for the restore, so depth is cut."""
    from repro.configs import get_config
    from repro.launch.train import run_training

    full = get_config("stablelm-3b")
    cfg = dataclasses.replace(full, n_periods=TRAIN_LAYERS)
    print(f"[c train] stablelm-3b cut: {cfg.n_layers} of {full.n_layers} "
          f"layers ({cfg.n_params() / 1e9:.3f}B of "
          f"{full.n_params() / 1e9:.3f}B parameters); d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.n_heads} heads kept", flush=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt") as ckpt, \
            clock.phase() as span:
        rep = run_training(cfg, steps=TRAIN_STEPS, batch=TRAIN_BATCH,
                           seq=TRAIN_SEQ, ckpt_dir=ckpt, fail_at=(FAIL_AT,),
                           fail_xid=94, verbose=False)
    expect_resume = FAIL_AT - FAIL_AT % max(TRAIN_STEPS // 5, 5)
    check(rep.steps_done == TRAIN_STEPS,
          f"trained {rep.steps_done}/{TRAIN_STEPS} steps")
    check(rep.n_failures == 1 and rep.restore_steps == [expect_resume],
          f"one XID, resumed at step {expect_resume} "
          f"(got {rep.restore_steps})")
    check(all(math.isfinite(x) for x in rep.losses), "every loss finite")
    report("c train", span, backend="xla",
           lanes=f"batch{TRAIN_BATCH}xseq{TRAIN_SEQ}",
           steps=rep.steps_done, xid=94, resumed_at=rep.restore_steps[0],
           saves=rep.checkpoint_saves, final_loss=f"{rep.final_loss:.4f}",
           parity="finite loss after resume")


def main() -> int:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 1
    clock = CompileClock()
    phase_wavefront_pallas(clock, phase_served(clock))
    phase_detector(clock)
    phase_train(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
