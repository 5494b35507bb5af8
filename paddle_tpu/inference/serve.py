"""Standalone serving of `jit.save` artifacts (deployment without the
training frontend).

Reference parity: the C++ AnalysisPredictor + C API
(paddle/fluid/inference/api/analysis_predictor.cc, inference/capi_exp/) are
the reference's deployable product: they load the saved inference program +
params and serve it with no Python training stack. TPU-native: the
`jit.save` artifact is serialized StableHLO (jax.export) + parameter
arrays; this module deserializes and executes it through PJRT using ONLY
`jax` and `numpy` — importing no paddle_tpu model classes, layers, or the
Tensor frontend (guarded by examples/inference_deploy.py with an import
hook).

Usage:
    python -m paddle_tpu.inference.serve ARTIFACT [--warmup N] [--bench N]
        [--http PORT]

  --bench runs N timed inferences on synthesized (shape-derived) inputs and
  prints one JSON line with p50/p90/p99 latency. --http serves POST /run
  with an .npz body of arrays inp0..inpK, answering an .npz of out0..outN.
  Parameters are made device-resident ONCE at load; benchmark inputs are
  transferred once and reused (pinned IO), so steady-state latency measures
  compute + output D2H only.

Artifact format: the safe ``paddle_tpu-npz1`` container
(paddle_tpu.inference.artifact) — a zip of ``meta.json`` + raw
``stablehlo.bin`` program bytes + raw ``param_*.bin`` array members. The
load path never unpickles: a malicious artifact can at most fail StableHLO
deserialization. Legacy pickle ``.pdmodel`` files (which DID execute
arbitrary code on load) are rejected with a re-export pointer.
"""
from __future__ import annotations

import argparse
import io
import json
import math
import time

import numpy as np

__all__ = ["Artifact", "build_http_server", "main"]


_SYNTH_DIM = 1  # symbolic/batch dims synthesize at 1 for warmup/bench


def synth_host_inputs(in_shapes):
    """Host arrays synthesized from an artifact's declared (shape, dtype)
    list — the one shape-synthesis rule, shared by the standalone Artifact
    and the in-process Predictor.warmup()."""
    return [np.zeros(tuple(d if isinstance(d, int) else _SYNTH_DIM
                           for d in shape), _np_dtype(dtype))
            for shape, dtype in in_shapes]


_BY_PATH: dict = {}


def _load_by_path(relpath: str):
    """Load a module of this package BY FILE PATH: standalone serving runs
    with an import hook that forbids every `paddle_tpu.*` import (the
    frontend-free guarantee), and the modules loaded this way need only
    the stdlib, numpy and jax."""
    if relpath not in _BY_PATH:
        import importlib.util
        import os

        p = os.path.normpath(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), relpath))
        name = "_serve_" + os.path.splitext(os.path.basename(p))[0]
        spec = importlib.util.spec_from_file_location(name, p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _BY_PATH[relpath] = mod
    return _BY_PATH[relpath]


def _artifact_mod():
    return _load_by_path("artifact.py")


def _np_dtype(s: str):
    return _artifact_mod().np_dtype(s)


class Artifact:
    """A loaded StableHLO deployment artifact: resident params + compiled
    call. No model-class import happens here or below."""

    def __init__(self, path: str, warmup: int = 0):
        import jax
        from jax import export as jexport

        if not path.endswith(".pdmodel"):
            path = path + ".pdmodel"
        # data-only members (meta.json / stablehlo.bin / param_*.bin);
        # legacy pickle artifacts raise with a re-export pointer
        blob = _artifact_mod().read_artifact(path)
        self._exported = jexport.deserialize(bytearray(blob["stablehlo"]))
        # params become device-resident once (the AnalysisPredictor's
        # weights-on-device analog); inference calls never re-upload them
        self._params = [jax.device_put(np.asarray(v))
                        for v in blob["params"]]
        jax.block_until_ready(self._params)
        self.in_shapes = blob.get("in_shapes", [])
        self.platform = jax.devices()[0].platform
        self._jax = jax
        if warmup:
            args = self.synth_inputs()
            for _ in range(warmup):
                jax.block_until_ready(self._exported.call(self._params,
                                                          args))

    def synth_inputs(self):
        """Device-resident inputs synthesized from the artifact's declared
        shapes (symbolic dims -> 1)."""
        arrays = [self._jax.device_put(a)
                  for a in synth_host_inputs(self.in_shapes)]
        self._jax.block_until_ready(arrays)
        return arrays

    def run(self, arrays):
        """One inference; returns numpy outputs."""
        outs = self._exported.call(self._params, list(arrays))
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        return [np.asarray(o) for o in outs]

    def bench(self, iters: int):
        """Timed inferences on pinned synthesized inputs; latency stats."""
        args = self.synth_inputs()
        lats = []
        for _ in range(iters):
            t0 = time.perf_counter()
            outs = self._exported.call(self._params, args)
            self._jax.block_until_ready(outs)
            lats.append((time.perf_counter() - t0) * 1e3)
        lats.sort()

        def pct(p):
            return round(lats[min(int(len(lats) * p / 100),
                                  len(lats) - 1)], 3)

        return {"iters": iters, "p50_ms": pct(50), "p90_ms": pct(90),
                "p99_ms": pct(99), "platform": self.platform}


DEFAULT_QUEUE_LIMIT = 32        # == FLAGS_serving_queue_limit default
DEFAULT_TIMEOUT_S = 60.0        # == FLAGS_serving_request_timeout_s default
DEFAULT_MAX_BODY_MB = 8         # == FLAGS_serving_max_body_mb default


def build_http_server(port: int, run_fn=None, generate_fn=None, *,
                      queue_limit: int = DEFAULT_QUEUE_LIMIT,
                      timeout_s: float = DEFAULT_TIMEOUT_S,
                      max_body_bytes: int = DEFAULT_MAX_BODY_MB << 20,
                      host: str = "127.0.0.1",
                      admit_fn=None, health_fn=None, stats_fn=None,
                      metrics_fn=None):
    """The serving HTTP front-end, dependency-injected so this module stays
    frontend-free (it imports no paddle_tpu):

      * POST /run      -> run_fn(list of np arrays) -> list of np arrays
                          (.npz body inp0..inpK, .npz answer out0..outN)
      * POST /generate -> generate_fn(payload dict, deadline) yielding event
                          dicts, streamed as one JSON line each (ndjson) —
                          the continuous-batching scheduler's token stream
                          when paddle_tpu.serving.ServingEngine.serve_http
                          injects it. A submitted prompt prefills through
                          the engine's packed multi-prompt frames (or is
                          posted to the prefill workers of a
                          disaggregated decode-role engine) before its
                          tokens stream; serving.replica.HTTPReplica is
                          the matching client, so a fleet Router drives
                          this endpoint exactly like an in-process
                          replica.
      * GET /healthz   -> health_fn() dict, answered as JSON (503 when the
                          dict carries ``"ok": False`` or health_fn raises)
      * GET /stats     -> stats_fn() dict as JSON — queue depth, in-flight
                          count, slot fill, retraces-after-warmup — so
                          liveness/readiness probes (and the fleet router)
                          never need a generate call. GETs bypass the
                          bounded POST queue: a saturated engine must still
                          answer its probes, that's the whole point.
      * GET /metrics   -> metrics_fn() string served as Prometheus text
                          exposition (format 0.0.4) — the observability
                          plane's scrape endpoint
                          (paddle_tpu.observability.metrics). Same
                          queue-bypass rule as the other probes.

    ``admit_fn(payload) -> None | dict`` is consulted BEFORE the 200 of a
    /generate: returning ``{"status": 503, "retry_after": 1.0, "message":
    ...}`` refuses the request with that status and a Retry-After header
    (admission control backpressure), instead of burying the refusal in a
    stream event after headers are already out. The dict contract (rather
    than a shared exception class) keeps this module frontend-free.

    Hardening (the old front-end was a single-threaded HTTPServer that
    head-of-line blocked on each request and read unbounded bodies):

      * ThreadingHTTPServer — a long /generate stream doesn't block /run
      * bounded request queue — more than `queue_limit` in-flight handlers
        are answered 503 immediately instead of queueing unboundedly
      * Content-Length cap — 413 past `max_body_bytes`; chunked/unknown
        length is rejected with 411, malformed with 400
      * per-request timeout — socket reads/writes (header phase included)
        and the queue wait are bounded by `timeout_s`; a /generate that
        exceeds it is terminated with a {"error": "timeout"} event, a /run
        that burned its budget queueing is refused before dispatch (the
        run_fn computation itself is not interruptible)
    """
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    slots = threading.BoundedSemaphore(queue_limit)

    class Handler(BaseHTTPRequestHandler):
        # bounds the REQUEST-LINE/HEADER phase too: without it a client
        # that connects and sends nothing parks a handler thread forever
        # without ever reaching do_POST's queue accounting
        timeout = timeout_s

        def _body(self):
            cl = self.headers.get("Content-Length")
            if cl is None:
                self.send_error(411, "Content-Length required")
                return None
            try:
                n = int(cl)
            except ValueError:
                self.send_error(400, "malformed Content-Length")
                return None
            if n < 0:
                self.send_error(400, "malformed Content-Length")
                return None
            if n > max_body_bytes:
                self.send_error(413, f"body exceeds {max_body_bytes} bytes")
                return None
            return self.rfile.read(n)

        def _json_reply(self, obj: dict, status: int = 200,
                        extra_headers: dict | None = None):
            data = json.dumps(obj).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            for k, v in (extra_headers or {}).items():
                self.send_header(k, str(v))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            # no slot accounting: probes must answer even when the POST
            # queue is saturated (a probe that 503s under load reads as a
            # dead replica and triggers a spurious drain)
            try:
                if self.path == "/healthz" and health_fn is not None:
                    h = dict(health_fn())
                    self._json_reply(h, 200 if h.get("ok", True) else 503)
                elif self.path == "/stats" and stats_fn is not None:
                    self._json_reply(dict(stats_fn()))
                elif self.path == "/metrics" and metrics_fn is not None:
                    data = str(metrics_fn()).encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                else:
                    self.send_error(404)
            except Exception as e:
                self._json_reply(
                    {"ok": False, "error": f"{type(e).__name__}: {e}"}, 503)

        def do_POST(self):
            if not slots.acquire(blocking=False):
                self.send_error(503, "request queue full")
                return
            try:
                self.connection.settimeout(timeout_s)
                deadline = time.monotonic() + timeout_s
                if self.path == "/run" and run_fn is not None:
                    self._do_run(deadline)
                elif self.path == "/generate" and generate_fn is not None:
                    self._do_generate(deadline)
                else:
                    self.send_error(404)
            finally:
                slots.release()

        def _do_run(self, deadline):
            body = self._body()
            if body is None:
                return
            # the deadline bounds the I/O phases (socket timeout) and the
            # queue wait; a request that already burned its budget getting
            # here is refused before dispatch (a running run_fn itself is
            # not interruptible from Python)
            if time.monotonic() > deadline:
                self.send_error(503, "request timed out in queue")
                return
            with np.load(io.BytesIO(body)) as z:
                args = [z[f"inp{i}"] for i in range(len(z.files))]
            outs = run_fn(args)
            buf = io.BytesIO()
            np.savez(buf, **{f"out{i}": o for i, o in enumerate(outs)})
            data = buf.getvalue()
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _do_generate(self, deadline):
            body = self._body()
            if body is None:
                return
            try:
                payload = json.loads(body)
            except Exception:
                self.send_error(400, "body must be JSON")
                return
            if admit_fn is not None:
                rej = admit_fn(payload)
                if rej:  # refuse BEFORE the 200: clean status + Retry-After
                    hdrs = {}
                    if rej.get("retry_after") is not None:
                        # RFC 9110 delta-seconds is an INTEGER; a float
                        # string gets discarded by strict clients
                        hdrs["Retry-After"] = math.ceil(
                            float(rej["retry_after"]))
                    self._json_reply(
                        {"error": rej.get("message", "rejected")},
                        int(rej.get("status", 503)), hdrs)
                    return
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            # close-delimited stream: one JSON line per event, flushed as
            # the scheduler emits tokens
            self.end_headers()
            try:
                for event in generate_fn(payload, deadline):
                    self.wfile.write((json.dumps(event) + "\n").encode())
                    self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):
                pass  # client went away; engine-side cancel already ran
            except Exception as e:
                # headers are already out — surface bad payloads and
                # engine errors as a terminal stream event, not a cut
                # connection
                try:
                    self.wfile.write(
                        (json.dumps({"error": f"{type(e).__name__}: {e}"})
                         + "\n").encode())
                except OSError:
                    pass

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer((host, port), Handler)
    srv.daemon_threads = True
    return srv


def _serve_http(artifact: Artifact, port: int,
                queue_limit: int = DEFAULT_QUEUE_LIMIT,
                timeout_s: float = DEFAULT_TIMEOUT_S,
                max_body_mb: int = DEFAULT_MAX_BODY_MB):
    srv = build_http_server(port, run_fn=artifact.run,
                            queue_limit=queue_limit, timeout_s=timeout_s,
                            max_body_bytes=max_body_mb << 20)
    print(json.dumps({"serving": True, "port": srv.server_port}), flush=True)
    srv.serve_forever()


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="serve a jit.save StableHLO artifact through PJRT "
                    "without the paddle_tpu model frontend")
    ap.add_argument("artifact")
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--bench", type=int, default=0)
    ap.add_argument("--http", type=int, default=None)
    ap.add_argument("--queue-limit", type=int, default=DEFAULT_QUEUE_LIMIT)
    ap.add_argument("--timeout-s", type=float, default=DEFAULT_TIMEOUT_S)
    ap.add_argument("--max-body-mb", type=int, default=DEFAULT_MAX_BODY_MB)
    args = ap.parse_args(argv)
    # before the first compile: JAX's persistent cache, placed from outside
    _load_by_path("../core/compile_cache.py").enable_compile_cache()
    art = Artifact(args.artifact, warmup=args.warmup)
    if args.bench:
        print(json.dumps(art.bench(args.bench)), flush=True)
    if args.http is not None:
        _serve_http(art, args.http, queue_limit=args.queue_limit,
                    timeout_s=args.timeout_s, max_body_mb=args.max_body_mb)
    return art


if __name__ == "__main__":
    main()
