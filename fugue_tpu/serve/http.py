"""The daemon's HTTP layer: a JSON API on the hardened server machinery
from :mod:`fugue_tpu.rpc.http`.

:class:`ServeHTTPServer` subclasses :class:`HTTPRPCServer`, inheriting
its threaded lifecycle (start/stop idempotence, wedged-shutdown
reporting) and the daemon-hardening conf — request body cap
(``fugue.rpc.http_server.max_body_bytes``), per-request read timeout
(``.read_timeout``) — while swapping the pickle RPC protocol handler for
a JSON router. Every response is JSON; failures are the structured
``{"error": {"error": <type>, "message": <str>}}`` payload, never a
traceback.
"""

import json
from typing import TYPE_CHECKING, Any

from fugue_tpu.obs.trace import NULL_SPAN, start_span
from fugue_tpu.rpc.http import (
    HardenedRequestHandler,
    HTTPRPCServer,
    structured_error,
)

if TYPE_CHECKING:  # pragma: no cover
    from fugue_tpu.serve.daemon import ServeDaemon


def json_default(obj: Any) -> Any:
    """JSON fallback for engine result cells: numpy/jax scalars unwrap
    via ``.item()``, dates/timestamps via ``.isoformat()``, anything
    else stringifies."""
    item = getattr(obj, "item", None)
    if callable(item):
        try:
            return item()
        except Exception:
            pass
    iso = getattr(obj, "isoformat", None)
    if callable(iso):
        return iso()
    return str(obj)


def dumps(payload: Any) -> bytes:
    return json.dumps(payload, default=json_default).encode("utf-8")


class _ServeAPIHandler(HardenedRequestHandler):
    # bound by the server factory (HTTPRPCServer.start_server)
    rpc_server: "ServeHTTPServer"

    def do_GET(self) -> None:  # noqa: N802 (stdlib naming)
        self._begin_request()
        self._route("GET", b"")

    def do_DELETE(self) -> None:  # noqa: N802
        # DELETE may carry a small JSON body (pipeline removal options);
        # absent Content-Length reads as empty, exactly like before
        self._begin_request()
        body = self.read_body()
        if body is None:
            return
        self._route("DELETE", body)

    def do_POST(self) -> None:  # noqa: N802
        # correlation id FIRST: even a 400/413 body rejection (written
        # inside read_body, before routing) must echo X-Request-Id
        self._begin_request()
        # the whole request on this thread: body, routing, encode, write
        with start_span("http.request") as sp:
            body = self.read_body()  # 413 already sent when over the cap
            if body is None:
                return
            self._route("POST", body, sp)

    def _begin_request(self) -> None:
        self._request_id = self.headers.get("X-Request-Id")

    def _route(self, method: str, body: bytes, span: Any = NULL_SPAN) -> None:
        try:
            payload = json.loads(body.decode("utf-8")) if body else {}
            if not isinstance(payload, dict):
                raise ValueError("request body must be a JSON object")
        except ValueError as ex:
            self.send_error_payload(400, ex)
            return
        daemon = self.rpc_server.daemon
        if method == "GET" and self.path.split("?", 1)[0] == "/v1/metrics":
            # Prometheus scrape: text exposition, not the JSON plane
            try:
                text = daemon.render_metrics()
            except Exception as ex:  # pragma: no cover - defensive
                self.send_error_payload(500, ex)
                return
            self._send_bytes(
                200,
                text.encode("utf-8"),
                "text/plain; version=0.0.4; charset=utf-8",
            )
            return
        status, resp, headers = daemon.handle_api(
            method, self.path, payload, request_id=self._request_id
        )
        if span:
            # job_id pairs this request with its job's serve.execute
            job_id = resp.get("job_id") if isinstance(resp, dict) else None
            span.set_attr(
                route=daemon._route_family(self.path),
                status=status,
                **({"job_id": job_id} if job_id else {}),
            )
        self._send_json(status, resp, headers)

    def _send_bytes(
        self,
        status: int,
        data: bytes,
        content_type: str,
        headers: Any = None,
    ) -> None:
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        merged = dict(headers or {})
        if "X-Request-Id" not in merged:
            # the router's echo when it ran; the raw inbound (or a
            # generated one) for failures answered before routing
            from fugue_tpu.serve.daemon import clean_request_id, new_request_id

            merged["X-Request-Id"] = (
                clean_request_id(getattr(self, "_request_id", None))
                or new_request_id()
            )
        for name, value in merged.items():
            # extra response headers from the router — Retry-After on
            # the backpressure/drain rejections, X-Request-Id everywhere
            self.send_header(name, str(value))
        self.end_headers()
        self.wfile.write(data)

    def _send_json(
        self, status: int, resp: Any, headers: Any = None
    ) -> None:
        with start_span("serve.encode") as sp:
            data = dumps(resp)
            if sp:
                sp.set_attr(bytes=len(data))
        self._send_bytes(status, data, "application/json", headers)

    def send_error_payload(self, status: int, ex: BaseException) -> None:
        self._send_json(status, {"error": structured_error(ex)})


class ServeHTTPServer(HTTPRPCServer):
    """The daemon's JSON API server. ``conf`` uses the same
    ``fugue.rpc.http_server.*`` keys as the RPC server (the daemon maps
    ``fugue.serve.host``/``.port`` onto them before construction)."""

    handler_class = _ServeAPIHandler

    def __init__(self, daemon: "ServeDaemon", conf: Any = None):
        super().__init__(conf)
        self.daemon = daemon
