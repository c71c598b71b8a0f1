"""HTTP front-end: Arrow IPC request/response over the shared endpoint.

Mounts onto the ONE process HTTP server (`utils.telemetry_http` — the
same ThreadingHTTPServer that serves /metrics and /healthz, so the
serving data plane and its observability surface share a port) via the
route-mount hook:

- ``POST /serve/<endpoint>`` — body: Arrow IPC stream bytes of the
  request frame (`io.frame_to_ipc_bytes` framing); response: Arrow IPC
  stream bytes of the outputs-only result frame. Headers:

  - ``X-TFS-Timeout-S`` (request) — per-request budget; enters a
    `deadline_scope`, so everything the request triggers (queueing,
    the coalesced dispatch, the response wait) shares one clock.
    Defaults to ``config.serve_default_timeout_s`` — a serving request
    is NEVER unbounded.
  - ``X-TFS-Request-Id`` (request, optional) — echoed back, stamped as
    the ``request=`` label on every verb span the request triggers
    (batched dispatches carry the joined ids), so `tfs.diagnostics()`
    and Chrome traces attribute work per request.

- ``GET /serve`` — JSON listing: registered endpoints (schemas,
  batchability, warmed rungs) + live batcher accounting.

Error mapping (typed, never a hang):

| raised                      | HTTP | extra                          |
|-----------------------------|------|--------------------------------|
| `OverloadError` (lane full, | 429  | ``Retry-After`` (whole s) from |
|  admission shed)            |      | the live latency histograms    |
| `DeadlineExceeded`          | 504  | budget/elapsed in the body     |
| `Cancelled`                 | 503  |                                |
| unknown endpoint            | 404  |                                |
| schema/body validation      | 400  |                                |
| anything else               | 500  |                                |

Security posture is the telemetry endpoint's: 127.0.0.1 by default, no
auth, exposing it further is a deliberate operator decision.
"""

from __future__ import annotations

import json
import math
import threading
import uuid
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Dict, Optional, Tuple

from ..runtime import deadline as _dl

__all__ = [
    "serve",
    "active",
    "draining",
    "set_draining",
    "ServingHandle",
    "ARROW_CONTENT_TYPE",
    "PREFIX",
]

PREFIX = "/serve"
ARROW_CONTENT_TYPE = "application/vnd.apache.arrow.stream"

_lock = threading.Lock()
_handle: Optional["ServingHandle"] = None

# Rolling-restart readiness (`tfs.serving.drain()`): while set, NEW
# serving requests shed with 503 and /healthz reports ready=false, so
# an external balancer stops routing here while in-flight batcher
# lanes finish. Cleared by serve() (a remount is a fresh replica) and
# serving.reset().
_draining = threading.Event()


def draining() -> bool:
    """True while `tfs.serving.drain()` is shedding new requests."""
    return _draining.is_set()


def set_draining(on: bool) -> None:
    if on:
        _draining.set()
    else:
        _draining.clear()


def _error_body(e: BaseException, **extra) -> bytes:
    payload = {"error": type(e).__name__, "message": str(e)}
    payload.update(extra)
    return json.dumps(payload).encode()


def _json(obj) -> Tuple[int, str, bytes, None]:
    return 200, "application/json", json.dumps(obj).encode(), None


def _handle_run(
    name: str, headers, body: bytes
) -> Tuple[int, str, bytes, Optional[Dict[str, str]]]:
    from .. import config as _config
    from ..io import frame_from_ipc_bytes, frame_to_ipc_bytes
    from ..utils import telemetry as _tele
    from .batcher import batcher as _the_batcher
    from . import registry as _registry

    rid = headers.get("X-TFS-Request-Id") or f"req-{uuid.uuid4().hex[:12]}"
    echo = {"X-TFS-Request-Id": rid}
    if _draining.is_set():
        # rolling restart: shed BEFORE any work — the balancer already
        # sees ready=false on /healthz; stragglers get a typed 503
        return 503, "application/json", json.dumps(
            {
                "error": "Draining",
                "message": (
                    "serving is draining for a rolling restart; retry "
                    "against another replica"
                ),
                "draining": True,
            }
        ).encode(), echo
    try:
        ep = _registry.get(name)
    except KeyError as e:
        return 404, "application/json", _error_body(e), echo
    try:
        timeout_hdr = headers.get("X-TFS-Timeout-S")
        timeout_s = (
            float(timeout_hdr)
            if timeout_hdr
            else float(_config.get().serve_default_timeout_s)
        )
        if not (timeout_s > 0):
            raise ValueError(
                f"X-TFS-Timeout-S must be > 0, got {timeout_s!r}"
            )
        if not body:
            raise ValueError("empty request body (expected Arrow IPC bytes)")
        frame = frame_from_ipc_bytes(body)
    except Exception as e:
        return 400, "application/json", _error_body(e), echo

    try:
        with _tele.request_scope(rid):
            with _dl.deadline_scope(
                timeout_s=timeout_s, verb=f"serve:{name}"
            ) as scope:
                # validates synchronously (a bad request fails alone,
                # before it can join a batch), may shed synchronously
                fut = _the_batcher().submit(ep, frame, request_id=rid)
                rem = scope.remaining()
                try:
                    result = fut.result(timeout=rem)
                except _FutureTimeout:
                    # give up our queue slot if the batch has not
                    # claimed it; the dispatcher drops cancelled work
                    fut.cancel()
                    raise _dl.DeadlineExceeded(
                        f"serve:{name}: request {rid} exceeded its "
                        f"budget ({timeout_s:.3f}s) waiting for dispatch",
                        verb=f"serve:{name}", budget_s=timeout_s,
                    )
        out = frame_to_ipc_bytes(result)
        return 200, ARROW_CONTENT_TYPE, out, echo
    except _dl.OverloadError as e:
        hdrs = dict(echo)
        hdrs["Retry-After"] = str(max(1, math.ceil(e.retry_after_s)))
        _incident(e, name, rid, 429)
        return 429, "application/json", _error_body(
            e,
            retry_after_s=e.retry_after_s,
            queue_depth=e.queue_depth,
            limit=e.limit,
        ), hdrs
    except _dl.DeadlineExceeded as e:
        _incident(e, name, rid, 504)
        return 504, "application/json", _error_body(
            e, budget_s=e.budget_s, elapsed_s=e.elapsed_s
        ), echo
    except _dl.Cancelled as e:
        _incident(e, name, rid, 503)
        return 503, "application/json", _error_body(e), echo
    except ValueError as e:
        return 400, "application/json", _error_body(e), echo
    except Exception as e:
        _incident(e, name, rid, 500)
        return 500, "application/json", _error_body(e), echo


def _incident(e: BaseException, name: str, rid: str, status: int) -> None:
    """Flight-recorder hook for a request mapped to an error status.
    Faults already captured at the verb layer are stamped with
    ``tfs_incident_id`` and dedup to the same bundle; a server-side
    failure (batcher future, IPC encode, a fresh 504 built here) gets
    its first capture with the serving context attached."""
    try:
        from ..runtime import blackbox as _blackbox

        _blackbox.capture(
            "serving", e, verb=f"serve:{name}",
            extra={"endpoint": name, "request_id": rid, "status": status},
        )
    except Exception:
        pass  # the recorder must never turn a 5xx into a crash


def _route(method: str, path: str, headers, body: bytes):
    """The mounted handler (`telemetry_http.mount` signature)."""
    from .batcher import batcher as _the_batcher
    from . import registry as _registry

    sub = path[len(PREFIX):].strip("/")
    if method == "GET":
        if not sub:
            return _json(
                {
                    "service": "tensorframes_tpu serving",
                    "draining": _draining.is_set(),
                    "endpoints": _registry.endpoints(),
                    "batcher": _the_batcher().snapshot(),
                }
            )
        try:
            return _json(_registry.get(sub).describe())
        except KeyError as e:
            return 404, "application/json", _error_body(e), None
    if method == "POST":
        if not sub or "/" in sub:
            return 404, "application/json", _error_body(
                KeyError(f"POST {path!r}: expected {PREFIX}/<endpoint>")
            ), None
        return _handle_run(sub, headers, body)
    return 405, "application/json", _error_body(
        ValueError(f"method {method} not allowed on {path!r}")
    ), None


class ServingHandle:
    """Handle to the mounted serving front-end. ``url`` points at the
    ``/serve`` prefix on the shared process server; ``close()``
    unmounts the routes (the shared server keeps running — stop it with
    ``tfs.telemetry.shutdown()``)."""

    def __init__(self, server):
        self._server = server
        self.host = server.host
        self.port = server.port

    @property
    def url(self) -> str:
        return f"{self._server.url}{PREFIX}"

    @property
    def running(self) -> bool:
        return self._server.running

    def close(self) -> None:
        global _handle
        from ..utils import telemetry_http as _http

        _http.unmount(PREFIX)
        with _lock:
            if _handle is self:
                _handle = None


def serve(
    port: Optional[int] = None, host: Optional[str] = None
) -> ServingHandle:
    """Mount the serving routes on the process HTTP server (starting it
    if none is running — ``port=0`` binds an ephemeral port) and return
    the handle. Registered endpoints become immediately servable; the
    same port keeps serving /metrics, /healthz, /diagnostics, /trace —
    the serving data plane and its autoscaling signals are one
    surface."""
    # The wire format is Arrow IPC: import pyarrow HERE, on the thread
    # that mounts the front-end, not lazily inside the handler threads.
    # A process whose first pyarrow import happens in one handler thread
    # while others already decode requests segfaults inside pyarrow
    # (seen with 8 concurrent clients against a fresh process).
    import pyarrow  # noqa: F401

    from ..utils import telemetry_http as _http

    srv = _http.active_server()
    if srv is None or not srv.running:
        srv = _http.serve(port=port if port is not None else 0, host=host)
    elif port not in (None, 0, srv.port):
        raise RuntimeError(
            f"process HTTP server already bound to port {srv.port}; "
            f"cannot serve on {port} (tfs.telemetry.shutdown() first)"
        )
    _http.mount(PREFIX, _route, replace=True)
    _draining.clear()  # a (re)mounted front-end is a ready replica
    handle = ServingHandle(srv)
    global _handle
    with _lock:
        _handle = handle
    from ..utils.log import get_logger

    get_logger("serving").info(
        "serving front-end mounted at %s (POST %s/<endpoint>)",
        handle.url, PREFIX,
    )
    return handle


def active() -> Optional[ServingHandle]:
    """The mounted front-end, if any."""
    with _lock:
        return _handle
