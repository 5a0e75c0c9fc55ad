"""Stdlib JSON front door for the what-if service.

A `ThreadingHTTPServer` (no dependency beyond the standard library, so
tier-1 stays hermetic) exposing the service core:

* ``POST /whatif`` — ``{"preset": name | "scenario": {...},
  "overrides": {...}, "seeds": N}`` -> the distributional answer
  (median/IQR/95%-CI per metric) with its provenance
  (``source``: cache / surface / engine) and per-request latency;
* ``GET /surface`` — the precomputed sweep surface's metadata (axes,
  grid size, error bound), or ``{"surface": null}`` when none is built;
* ``GET /healthz`` — liveness;
* ``GET /stats`` — queries, cache hit/miss/eviction counts, coalescer
  window/dedup counters and queue waits (count, total, maximum), engine
  passes, uptime.

Run it:

    PYTHONPATH=src python -m repro.serve.http --port 8777 --surface

    curl -s localhost:8777/whatif -d '{"preset": "flaky-fabric",
                                       "seeds": 32}'
"""
from __future__ import annotations

import argparse
import json
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from repro.compile_cache import use_compile_cache
from repro.ops.scenario import get_scenario
from repro.serve.service import (ServiceConfig, WhatIfService,
                                 scenario_from_request)
from repro.serve.surface import SurfaceSpec, SweepSurface

__all__ = ["WhatIfHTTPServer", "make_server", "main"]

_MAX_BODY = 1 << 20                 # 1 MiB: a scenario spec is ~1 KiB


class WhatIfHTTPServer(ThreadingHTTPServer):
    """One service instance shared by all handler threads."""

    daemon_threads = True

    def __init__(self, addr, service: WhatIfService, verbose: bool = False):
        self.service = service
        self.verbose = verbose
        super().__init__(addr, _Handler)


class _Handler(BaseHTTPRequestHandler):
    server: WhatIfHTTPServer

    # -- plumbing -----------------------------------------------------------

    def log_message(self, fmt, *args):             # noqa: A002
        if self.server.verbose:
            super().log_message(fmt, *args)

    def _reply(self, code: int, payload: dict) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _error(self, code: int, message: str) -> None:
        self._reply(code, {"error": message})

    # -- routes -------------------------------------------------------------

    def do_GET(self) -> None:                      # noqa: N802
        svc = self.server.service
        if self.path == "/healthz":
            self._reply(200, {"ok": True})
        elif self.path == "/stats":
            self._reply(200, svc.stats())
        elif self.path == "/surface":
            self._reply(200, {"surface": svc.surface.info()
                              if svc.surface else None})
        else:
            self._error(404, f"unknown path {self.path!r} "
                             "(try /whatif, /surface, /healthz, /stats)")

    def do_POST(self) -> None:                     # noqa: N802
        if self.path != "/whatif":
            self._error(404, f"unknown path {self.path!r} (POST /whatif)")
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
        except ValueError:
            length = -1
        if not 0 < length <= _MAX_BODY:
            self._error(413 if length > _MAX_BODY else 400,
                        "body required (JSON query, <= 1 MiB)")
            return
        try:
            payload = json.loads(self.rfile.read(length))
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            self._error(400, f"bad JSON: {e}")
            return
        svc = self.server.service
        try:
            scenario = scenario_from_request(payload)
            seeds = payload.get("seeds")
            answer = svc.query(scenario,
                               None if seeds is None else int(seeds))
        except (KeyError, ValueError, TypeError) as e:
            self._error(400, str(e))
            return
        self._reply(200, answer.to_dict())


def make_server(service: WhatIfService, host: str = "127.0.0.1",
                port: int = 0, verbose: bool = False) -> WhatIfHTTPServer:
    """Bind (port 0 = ephemeral, for tests); caller runs serve_forever."""
    return WhatIfHTTPServer((host, port), service, verbose=verbose)


def main(argv: Optional[list] = None) -> None:
    ap = argparse.ArgumentParser(
        description="what-if campaign query service (JSON over HTTP)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8777)
    ap.add_argument("--window-ms", type=float, default=20.0,
                    help="request-coalescing window: concurrent queries "
                         "arriving within it share one stacked engine "
                         "pass (0 disables coalescing)")
    ap.add_argument("--cache-capacity", type=int, default=256,
                    help="LRU entries of finished distributions "
                         "(0 disables the cache)")
    ap.add_argument("--default-seeds", type=int, default=None,
                    help="Monte Carlo seeds per query when the request "
                         "does not set 'seeds'")
    ap.add_argument("--wavefront-backend", default="auto",
                    choices=("auto", "numpy", "xla", "pallas"),
                    help="campaign engine backend for live passes")
    ap.add_argument("--surface", action="store_true",
                    help="precompute the preset sweep surface (node "
                         "count x nvlink tilt x checkpoint cadence "
                         "around --surface-base) before serving; near-"
                         "miss queries interpolate instead of simulating")
    ap.add_argument("--surface-base", default="paper-faithful",
                    help="preset the surface grid is built around")
    ap.add_argument("--surface-days", type=float, default=None,
                    help="override the surface base campaign length "
                         "(shorter builds faster)")
    ap.add_argument("--verbose", action="store_true",
                    help="log one line per request")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = ServiceConfig(window_s=args.window_ms / 1e3,
                        coalesce=args.window_ms > 0,
                        cache_capacity=args.cache_capacity,
                        wavefront_backend=args.wavefront_backend)
    if args.default_seeds is not None:
        cfg.default_seeds = args.default_seeds
    surface = None
    if args.surface:
        base = get_scenario(args.surface_base)
        if args.surface_days is not None:
            base = base.replace(duration_days=args.surface_days)
        spec = SurfaceSpec(base=base)
        print(f"building surface: {spec.base.name}, "
              f"{len(spec.n_nodes)}x{len(spec.tilts)}x"
              f"{len(spec.ckpt_hours)} grid x {spec.seeds} seeds…",
              flush=True)
        surface = SweepSurface(
            spec, wavefront_backend=args.wavefront_backend).build()
        print(f"surface built in {surface.build_wall_s:.1f} s")
    service = WhatIfService(cfg, surface=surface)
    server = make_server(service, args.host, args.port,
                         verbose=args.verbose)
    host, port = server.server_address[:2]
    print(f"what-if service on http://{host}:{port} "
          f"(window {args.window_ms:.0f} ms, cache "
          f"{args.cache_capacity}, surface "
          f"{'on' if surface else 'off'}) — POST /whatif", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        service.close()


if __name__ == "__main__":
    main()
