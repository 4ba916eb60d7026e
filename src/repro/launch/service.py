"""HTTP front door for the continuous-batching layout engine.

A thin stdlib ``http.server`` layer over
``serve.engine.ContinuousLayoutService`` — the fixinventory-style
multi-tenant scenario: every user's graph laid out on demand by one
always-on engine, requests joining the wave scheduler mid-flight
(DESIGN.md §11).

    PYTHONPATH=src python -m repro.launch.service --port 8080

    POST /layout   {"edges": [[u, v], ...], "n": 123, "priority": 0,
                    "deadline_s": 30.0, "seed": 7, "engine": "stress"}
        → 200 {"rid", "pos": [[x, y], ...], "levels", "latency_s"}
        → 400 malformed graph            (validation at the boundary)
        → 429 admission queue full       (bounded-queue backpressure)
        → 504 deadline exceeded / timeout
    GET  /healthz  → 200 ok
    GET  /stats    → engine counters + compile-cache stats (JSON)
    GET  /metrics  → Prometheus text exposition of the metrics registry
                     (cache hit/miss, padding occupancy, queue depth,
                     latency histograms — DESIGN.md §12)

``--trace out.json`` enables the span tracer for the server's lifetime
and writes a Chrome/Perfetto trace-event timeline on shutdown.

``--smoke`` starts the server on an ephemeral port, POSTs a few graphs
from client threads, asserts the responses, and shuts down (CI-friendly
self-test; tests/test_service.py drives the same path in-process).
"""
from __future__ import annotations

import argparse
import json
import threading
from concurrent.futures import CancelledError
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np


def make_server(svc, host: str = "127.0.0.1", port: int = 0,
                default_timeout_s: float = 300.0) -> ThreadingHTTPServer:
    """Build (but do not start) the HTTP server wrapping ``svc``.

    ``ThreadingHTTPServer`` gives one thread per connection, so a handler
    blocking on its request's Future stalls nobody else — the engine
    worker keeps admitting other requests between waves.
    """
    from repro.serve.engine import DeadlineExceeded, EngineBusy

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):       # quiet: CI logs stay readable
            pass

        def _json(self, code: int, payload: dict) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/healthz":
                self._json(200, {"ok": True})
            elif self.path == "/stats":
                from repro.core import bucketing
                self._json(200, {"engine": svc.stats(),
                                 "compile_cache": bucketing.cache_stats()})
            elif self.path == "/metrics":
                from repro.obs import metrics as obs_metrics
                body = obs_metrics.REGISTRY.to_prometheus().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self._json(404, {"error": f"no route {self.path}"})

        def do_POST(self):
            if self.path != "/layout":
                self._json(404, {"error": f"no route {self.path}"})
                return
            try:
                size = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(size) or b"{}")
                edges = np.asarray(body.get("edges", []), dtype=np.int64)
                n = body["n"]
                timeout = float(body.get("timeout_s", default_timeout_s))
            except (KeyError, ValueError, TypeError, json.JSONDecodeError) as e:
                self._json(400, {"error": f"bad request: {e}"})
                return
            try:
                req = svc.submit(
                    edges, n, priority=int(body.get("priority", 0)),
                    deadline_s=body.get("deadline_s"),
                    seed=body.get("seed"),
                    engine=body.get("engine"))
            except ValueError as e:
                self._json(400, {"error": str(e)})
                return
            except EngineBusy as e:
                self._json(429, {"error": str(e)})
                return
            try:
                pos, stats = req.result(timeout)
            except DeadlineExceeded as e:
                self._json(504, {"error": str(e), "rid": req.rid})
                return
            except CancelledError:
                self._json(409, {"error": "request cancelled",
                                 "rid": req.rid})
                return
            except TimeoutError:
                svc.cancel(req)
                self._json(504, {"error": f"no result in {timeout}s",
                                 "rid": req.rid})
                return
            self._json(200, {"rid": req.rid,
                             "pos": np.asarray(pos, np.float32).tolist(),
                             "levels": stats.levels,
                             "latency_s": round(req.latency or 0.0, 6)})

    return ThreadingHTTPServer((host, port), Handler)


def smoke() -> None:
    """Self-test: serve three graphs over HTTP, assert parity + stats."""
    import urllib.request

    from repro.core import LayoutConfig, multigila_layout
    from repro.graphs import generators as G
    from repro.serve.engine import ContinuousLayoutService

    cfg = LayoutConfig(seed=0)
    svc = ContinuousLayoutService(cfg, max_lanes=8)
    httpd = make_server(svc)
    host, port = httpd.server_address
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        graphs = [G.delaunay(90, 7 + i) for i in range(3)]
        for i, (e, n) in enumerate(graphs):
            payload = json.dumps({"edges": e.tolist(), "n": int(n),
                                  "seed": 7 + i}).encode()
            with urllib.request.urlopen(
                    f"http://{host}:{port}/layout", data=payload,
                    timeout=600) as resp:
                out = json.loads(resp.read())
            import dataclasses
            ref, _ = multigila_layout(
                e, n, dataclasses.replace(cfg, seed=7 + i))
            got = np.asarray(out["pos"], np.float32)
            assert got.shape == (n, 2), got.shape
            assert np.array_equal(got, np.asarray(ref, np.float32)), \
                "HTTP result diverged from the dedicated driver"
            print(f"[service] graph {i}: n={n} levels={out['levels']} "
                  f"latency={out['latency_s']}s", flush=True)
        with urllib.request.urlopen(f"http://{host}:{port}/stats",
                                    timeout=60) as resp:
            stats = json.loads(resp.read())
        assert stats["engine"]["completed"] == 3, stats["engine"]["completed"]
        with urllib.request.urlopen(f"http://{host}:{port}/metrics",
                                    timeout=60) as resp:
            prom = resp.read().decode()
        assert "gila_compile_cache_hits_total" in prom, prom[:400]
        assert "gila_wave_padded_slots_total" in prom, prom[:400]
        eng = {k: v for k, v in stats["engine"].items() if k != "metrics"}
        print(f"[service] smoke OK: {eng}", flush=True)
    finally:
        httpd.shutdown()
        svc.close()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--max-lanes", type=int, default=32,
                    help="concurrent component lanes the engine runs")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="admission queue bound (backpressure above it)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="record a Chrome/Perfetto trace for the server's "
                         "lifetime; written on shutdown")
    ap.add_argument("--smoke", action="store_true",
                    help="serve 3 graphs over HTTP on an ephemeral port, "
                         "assert parity, exit")
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        smoke()
        return

    from repro.core import LayoutConfig
    from repro.obs import trace as obs_trace
    from repro.serve.engine import ContinuousLayoutService

    if args.trace:
        obs_trace.enable()
    svc = ContinuousLayoutService(LayoutConfig(seed=args.seed),
                                  max_queue=args.max_queue,
                                  max_lanes=args.max_lanes)
    httpd = make_server(svc, host=args.host, port=args.port)
    print(f"[service] continuous-batching layout engine on "
          f"http://{args.host}:{httpd.server_address[1]} "
          f"(max_lanes={args.max_lanes}, max_queue={args.max_queue})",
          flush=True)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.shutdown()
        svc.close()
        if args.trace:
            obs_trace.export(args.trace)
            print(f"[service] wrote trace to {args.trace}", flush=True)


if __name__ == "__main__":
    main()
