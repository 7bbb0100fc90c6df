"""HTTP surface of the serving tier.

Routes (all JSON):

- `GET  /health`     liveness (+ hosted model names)
- `GET  /healthz`    readiness: `{"status": "warming"|"ready"|"failed",
                     "models": …}` ("failed": the warm-up raised)
- `GET  /metrics`    Prometheus scrape (`?format=json` for the snapshot)
- `GET  /v1/models`  per-model status / residency / HBM estimate / loaded
                     LoRA adapters (name, rank, bytes, pinned)
- `GET  /v1/tenants` per-(model, adapter) cost rollups from the request
                     ledger: requests, tokens in/out, attributed
                     device-seconds, mean queue wait, adapter HBM share
- `POST /admin/flight-dump`  trigger a flight-recorder bundle
                     (`{"reason"?}`); rate-limited per reason, so the
                     response's `"path"` is null when a recent dump for
                     the same reason already exists
- `POST /predict`    `{"data": [[...]], "model"?, "adapter"?,
                       "timeout_ms"?}`
- `POST /generate`   `{"prompt_ids": [...], "n_steps": N, "temperature"?,
                       "top_k"?, "top_p"?, "seed"?, "eos_id"?, "model"?,
                       "adapter"?, "timeout_ms"?}`

`"adapter"` selects a LoRA delta loaded next to the model's resident base
(`InferenceServer.load_adapter`); an unknown name is a 400.

When the server is a fleet member (`server.fleet_replica` set by
`serving/fleet.py`), two admin routes appear and every predict/generate
passes through the replica's admission seam first — deterministic fleet
faults fire there and a draining replica refuses there with a clean 503:

- `POST /admin/drain`   start a graceful drain (returns immediately)
- `POST /admin/reload`  `{"path": ...}` drained rolling update: swap the
                        checkpoint, AOT-warm it, re-join the fleet; the
                        response carries the compile/warm ledger

Failure mapping is a table over the typed errors in `serving/errors.py`:
the status comes off the exception class, `Retry-After` appears whenever
the error carries one (load shedding, warming, eviction reload), plain
`TimeoutError` is a 504, malformed payloads are a 400 — a traceback-500
is reserved for genuinely unexpected failures."""

from __future__ import annotations

import json
from http.server import BaseHTTPRequestHandler
from typing import Optional
from urllib.parse import parse_qs, urlparse

from deeplearning4j_tpu import observability as _obs
from deeplearning4j_tpu.observability import propagate as _prop
from deeplearning4j_tpu.serving.errors import ServingError


def make_handler(server):
    """Build the request-handler class bound to one `InferenceServer`."""

    class Handler(BaseHTTPRequestHandler):
        # Keep-alive: the federation aggregator (and the router's load
        # poll) scrape this surface continuously — re-dialing TCP and
        # spawning a fresh handler thread per poll is pure overhead.
        # Every response path sets Content-Length, which HTTP/1.1
        # persistence requires.
        protocol_version = "HTTP/1.1"

        def log_message(self, *args):
            pass

        def _json(self, obj, code=200, headers=None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _error(self, e: Exception):
            if isinstance(e, ServingError):
                headers = ({"Retry-After": str(e.retry_after)}
                           if e.retry_after is not None else None)
                return self._json(e.payload(), e.status, headers=headers)
            if isinstance(e, TimeoutError):
                return self._json({"error": str(e)}, 504)
            if isinstance(e, (KeyError, ValueError, json.JSONDecodeError)):
                return self._json({"error": f"bad request: {e}"}, 400)
            return self._json({"error": str(e)}, 500)

        # ------------------------------------------------------------- GET

        def do_GET(self):
            url = urlparse(self.path)
            if url.path == "/health":
                try:
                    model = type(server.net).__name__
                except Exception:
                    model = None
                self._json({"status": "ok", "model": model,
                            "models": server.models.names()})
            elif url.path == "/healthz":
                statuses = {row["name"]: row["status"]
                            for row in server.models.snapshot()}
                self._json({"status": server._status, "models": statuses})
            elif url.path == "/metrics":
                q = parse_qs(url.query)
                fmt = (q.get("format") or ["prometheus"])[0]
                names = (q["names"][0].split(",") if q.get("names")
                         else None)
                body, ctype = _obs.prometheus_payload(fmt, names=names)
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif url.path == "/api/trace":
                # This process's span ring, scrape-able by the federation
                # aggregator (same shape the UIServer exports). `since`
                # is the incremental cursor: only events recorded after
                # that `seq` are shipped.
                q = parse_qs(url.query)
                since = int(q["since"][0]) if q.get("since") else None
                self._json(_obs.tracer.export_chrome(since=since))
            elif url.path == "/v1/models":
                self._json({"models": server.models.snapshot()})
            elif url.path == "/v1/tenants":
                try:
                    self._json({"tenants": server.tenant_snapshot()})
                except Exception as e:
                    self._error(e)
            else:
                self._json({"error": "not found",
                            "routes": ["/health", "/healthz", "/metrics",
                                       "/api/trace", "/v1/models",
                                       "/v1/tenants", "/predict",
                                       "/generate",
                                       "/admin/flight-dump"]}, 404)

        # ------------------------------------------------------------ POST

        def _payload(self) -> dict:
            length = int(self.headers.get("Content-Length", 0))
            return json.loads(self.rfile.read(length))

        def _timeout_s(self, payload: dict) -> Optional[object]:
            from deeplearning4j_tpu.serving.server import _UNSET

            ms = payload.get("timeout_ms")
            return _UNSET if ms is None else float(ms) / 1000.0

        def _check_ready(self, name: Optional[str]) -> Optional[dict]:
            """503 + Retry-After while the server (or the target model) is
            warming: never park a caller behind an XLA compile."""
            if server._status == "warming":
                return {"error": "warming up", "status": server._status}
            if name is not None:
                model = server.models._models.get(name)
                if (model is not None and model.resident
                        and not model.ready.is_set()):
                    return {"error": f"model {name!r} is warming",
                            "status": "warming"}
            return None

        def do_POST(self):
            if self.path == "/predict":
                return self._post_predict()
            if self.path == "/generate":
                return self._post_generate()
            if self.path == "/admin/flight-dump":
                return self._post_flight_dump()
            replica = getattr(server, "fleet_replica", None)
            if replica is not None and self.path == "/admin/drain":
                return self._post_drain(replica)
            if replica is not None and self.path == "/admin/reload":
                return self._post_reload(replica)
            return self._json({"error": "not found"}, 404)

        def _admit(self, route: str):
            """Fleet admission seam: fleet faults fire here and a
            draining replica 503s here, BEFORE the request touches the
            batcher. Returns the replica when the caller owes a
            `request_done()`, None for a non-fleet server."""
            replica = getattr(server, "fleet_replica", None)
            if replica is None:
                return None
            replica.on_request(route)
            return replica

        def _trace_span(self, route: str):
            """Replica-side request span, parented to the caller's context
            when the request carried an ``X-DL4J-Trace`` header (the
            router attaches one per attempt)."""
            rctx = _prop.parse(self.headers.get(_prop.TRACE_HEADER))
            return _obs.tracer.span(f"replica.{route}", cat="serving",
                                    parent_ctx=rctx, route=route)

        def _post_predict(self):
            with self._trace_span("predict") as sp, _prop.bound(sp.ctx()):
                admitted = None
                try:
                    payload = self._payload()
                    name = payload.get("model")
                    warming = self._check_ready(name)
                    if warming is not None:
                        return self._json(warming, 503,
                                          headers={"Retry-After": "1"})
                    admitted = self._admit("predict")
                    preds = server.predict(
                        payload["data"], model=name,
                        adapter=payload.get("adapter"),
                        timeout_s=self._timeout_s(payload))
                except Exception as e:
                    return self._error(e)
                finally:
                    if admitted is not None:
                        admitted.request_done()
                self._json({"predictions": preds.tolist()})

        def _post_generate(self):
            with self._trace_span("generate") as sp, _prop.bound(sp.ctx()):
                admitted = None
                try:
                    payload = self._payload()
                    name = payload.get("model")
                    warming = self._check_ready(name)
                    if warming is not None:
                        return self._json(warming, 503,
                                          headers={"Retry-After": "1"})
                    sampling = {k: payload[k] for k in
                                ("temperature", "top_k", "top_p", "seed",
                                 "eos_id") if k in payload}
                    admitted = self._admit("generate")
                    ids = server.generate(
                        payload["prompt_ids"], int(payload["n_steps"]),
                        model=name, adapter=payload.get("adapter"),
                        timeout_s=self._timeout_s(payload), **sampling)
                except Exception as e:
                    return self._error(e)
                finally:
                    if admitted is not None:
                        admitted.request_done()
                self._json({"ids": [int(t) for t in ids]})

        # ----------------------------------------------------------- admin

        def _post_flight_dump(self):
            """SLO-page hook: the router's burn-rate engine POSTs here when
            a paging burn implicates this replica. force=False rides the
            recorder's per-reason rate limit — repeated pages within the
            window return path=null instead of a second bundle, which is
            how one sustained breach yields exactly one bundle."""
            try:
                length = int(self.headers.get("Content-Length", 0))
                payload = (json.loads(self.rfile.read(length))
                           if length else {})
                reason = str(payload.get("reason") or "admin")
                path = _obs.flight.dump(reason=reason, force=False)
            except Exception as e:
                return self._error(e)
            self._json({"path": None if path is None else str(path)})

        def _post_drain(self, replica):
            import threading

            threading.Thread(target=replica.drain,
                             name="dl4j-admin-drain", daemon=True).start()
            self._json({"status": "draining", "inflight": replica.inflight()})

        def _post_reload(self, replica):
            try:
                payload = self._payload()
                summary = replica.reload(payload["path"],
                                         warm=bool(payload.get("warm", True)))
            except Exception as e:
                return self._error(e)
            self._json(summary)

    return Handler
