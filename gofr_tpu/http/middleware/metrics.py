"""Metrics middleware: per-request latency histogram + inflight gauge.

Capability parity with ``pkg/gofr/http/middleware/metrics.go:21-42``
(``app_http_response`` histogram labeled path/method/status). Two ISSUE 2
additions: an escaped handler exception is observed as status=500 before
re-raising (previously failures bypassed the histogram entirely, so error
storms were invisible in latency dashboards), and ``app_http_inflight``
counts requests between arrival and response — the saturation signal a
rate-of-completions histogram cannot give while requests are stuck.
A streamed reply also records ``app_http_stream_self_seconds``: the
server's own share of it (``StreamBody.self_s``).
"""

from __future__ import annotations

import time

from gofr_tpu.http.router import Middleware, WireHandler
from gofr_tpu.metrics import Manager


def metrics_middleware(manager: Manager) -> Middleware:
    def middleware(next_handler: WireHandler) -> WireHandler:
        async def handle(request):
            start = time.perf_counter()
            # label by the matched route template, never the raw path: a
            # path with an embedded id (/debug/tracez/{trace_id}) would
            # mint one time series per request (GT008); unmatched paths
            # collapse into one bucket for the same reason
            route = getattr(request, "route", "") or "unmatched"
            manager.delta_updown_counter("app_http_inflight", 1.0)
            inflight_open = True

            def settle() -> None:
                nonlocal inflight_open
                if inflight_open:
                    inflight_open = False
                    manager.delta_updown_counter("app_http_inflight", -1.0)

            try:
                status, headers, body = await next_handler(request)
            except Exception:
                # the handler layer normally converts failures to a 500
                # response; anything escaping past it would otherwise
                # never reach the histogram
                manager.record_histogram(
                    "app_http_response", time.perf_counter() - start,
                    path=route, method=request.method, status="500")
                settle()
                raise
            from gofr_tpu.http.response import StreamBody
            if isinstance(body, StreamBody):
                # a stream's latency is its full production time, and a
                # producer failure mid-stream is a 500, not the header
                # status — observe at completion instead of header time
                def observe(ok: bool, messages: int,
                            status=status) -> None:
                    manager.record_histogram(
                        "app_http_response", time.perf_counter() - start,
                        path=route, method=request.method,
                        status=str(status if ok else 500))
                    # the server's share of that time: framing and writes
                    manager.record_histogram(
                        "app_http_stream_self_seconds", body.self_s,
                        path=route)
                    settle()

                body.on_complete(observe)
            else:
                manager.record_histogram(
                    "app_http_response", time.perf_counter() - start,
                    path=route, method=request.method,
                    status=str(status),
                )
                settle()
            return status, headers, body
        return handle
    return middleware
