"""Standalone in-sandbox executor HTTP server (Python implementation).

Serves the executor wire contract on the pod network, identical to the native
C++ server (executor/server.cpp) and to the reference's Rust server
(executor/server.rs:186-192):

- ``PUT  /workspace/{path}``  — stream request body into the workspace
- ``GET  /workspace/{path}``  — stream file back (404 if absent)
- ``POST /execute``           — ``{source_code, env?, timeout?}`` →
                                ``{stdout, stderr, exit_code, files[]}``
- ``GET  /healthz``           — readiness (new; the reference relied solely on
                                k8s pod Ready)

This Python server is (a) the development/test double for the pod HTTP seam —
the fake the reference never had (SURVEY.md §4) — and (b) a fallback pod
entrypoint where the C++ binary isn't built. Run:

    python -m bee_code_interpreter_tpu.runtime.executor_server

Env: APP_LISTEN_ADDR (default 0.0.0.0:8000), APP_WORKSPACE (default
/workspace), APP_REQUIREMENTS / APP_REQUIREMENTS_SKIP (preinstalled-set files,
reference server.rs:198-201), APP_DISABLE_DEP_INSTALL, APP_SHIM_DIR,
APP_LOG_FORMAT (``json`` for structured one-line records).

Observability (docs/observability.md): the control plane sends a W3C
``traceparent`` plus ``X-Request-Id`` on every data-plane call; this server
adopts both — the request id lands on every pod-side log record, the trace
continues under the same trace_id (server-side spans retained in a small
local store), and the id is echoed back in the response headers.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os

from aiohttp import web

from bee_code_interpreter_tpu.observability import (
    REQUEST_ID_HEADER,
    JsonLogFormatter,
    Tracer,
    TraceStore,
    parse_traceparent,
)
from bee_code_interpreter_tpu.runtime.dep_guess import load_requirements_set
from bee_code_interpreter_tpu.runtime.executor_core import ExecutorCore
from bee_code_interpreter_tpu.utils.request_id import (
    RequestIdLoggingFilter,
    request_id_context_var,
)

logger = logging.getLogger(__name__)


def create_app(
    core: ExecutorCore,
    tracer: Tracer | None = None,
    warm_error: str | None = None,
) -> web.Application:
    app = web.Application(client_max_size=1 << 30)
    # Pod-local retention only: the edge's store is the one an operator
    # queries; this one exists so in-pod spans/logs still correlate when a
    # pod is inspected directly.
    tracer = tracer or Tracer(store=TraceStore(max_traces=64, slowest_keep=8))

    @web.middleware
    async def trace_context_middleware(request: web.Request, handler):
        rid = request.headers.get(REQUEST_ID_HEADER)
        if rid:
            # Adopt the edge's id: every log record this request produces
            # (dep install, subprocess failures) correlates with the edge.
            request_id_context_var.set(rid)
        ctx = parse_traceparent(request.headers.get("traceparent"))
        if ctx is not None:
            trace_id, parent_span_id = ctx
            with tracer.trace(
                f"executor:{request.path}",
                trace_id=trace_id,
                parent_span_id=parent_span_id,
                request_id=rid,
            ):
                response = await handler(request)
        else:
            response = await handler(request)
        if rid:
            response.headers.setdefault(REQUEST_ID_HEADER, rid)
        return response

    app.middlewares.append(trace_context_middleware)

    async def upload_file(request: web.Request) -> web.Response:
        try:
            path = core.resolve(request.match_info["path"])
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "wb") as f:
            async for chunk in request.content.iter_chunked(1 << 20):
                f.write(chunk)
        return web.Response(status=204)

    async def download_file(request: web.Request) -> web.StreamResponse:
        try:
            path = core.resolve(request.match_info["path"])
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        if not path.is_file():
            return web.Response(status=404)
        return web.FileResponse(path)

    async def delete_file(request: web.Request) -> web.Response:
        """Remove one workspace file (sessions use this for rollback: files
        created after a checkpoint must not survive restoring it). 404 for
        a path that isn't there — callers treat that as already-gone."""
        try:
            path = core.resolve(request.match_info["path"])
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        if not path.is_file():
            return web.Response(status=404)
        path.unlink(missing_ok=True)
        return web.Response(status=204)

    async def execute(request: web.Request) -> web.Response:
        body = await request.json()
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        logger.info("Executing sandboxed code (%d bytes)", len(body["source_code"]))
        outcome = await core.execute(
            source_code=body["source_code"],
            env=body.get("env") or {},
            timeout_s=body.get("timeout"),
            # Edge dep pre-resolution (docs/analysis.md): with a prediction
            # attached, the core skips its own AST scan.
            predicted_deps=body.get("predicted_deps"),
        )
        logger.info("Sandboxed execution finished: exit_code=%s", outcome.exit_code)
        return web.json_response(
            {
                "stdout": outcome.stdout,
                "stderr": outcome.stderr,
                "exit_code": outcome.exit_code,
                "files": outcome.files,
                # additive diagnostic, mirrors the C++ server's field
                "duration_ms": (loop.time() - t0) * 1000,
                # per-execution resource accounting (docs/observability.md):
                # rusage deltas + wall + workspace byte deltas, measured by
                # ExecutorCore; the control-plane driver propagates this
                # into ExecuteResponse.usage.
                "usage": outcome.usage,
            }
        )

    async def execute_stream(request: web.Request) -> web.StreamResponse:
        """Streaming twin of ``POST /execute``: newline-delimited JSON
        events, one per output chunk —

            {"stream": "stdout"|"stderr", "data": "<text>"}\\n

        — closed by a terminal event carrying the exact non-streaming
        envelope (plus ``duration_ms``/``usage``):

            {"event": "end", "stdout": ..., "stderr": ..., "exit_code": ...,
             "files": [...], "duration_ms": ..., "usage": {...}}\\n

        Chunked transfer with per-event flush, so the control plane (and
        through it an SSE client) sees output the moment the sandboxed
        process writes it, not when the run ends."""
        body = await request.json()
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        logger.info(
            "Executing sandboxed code, streaming (%d bytes)",
            len(body["source_code"]),
        )
        response = web.StreamResponse(
            headers={"Content-Type": "application/x-ndjson"}
        )
        response.enable_chunked_encoding()
        await response.prepare(request)
        gen = core.execute_stream(
            source_code=body["source_code"],
            env=body.get("env") or {},
            timeout_s=body.get("timeout"),
            predicted_deps=body.get("predicted_deps"),
        )
        try:
            await _pump_stream(gen, response, loop, t0)
        except ConnectionResetError:
            # The consumer vanished mid-stream: expected (a dead SSE
            # client upstream), not an error worth a traceback — the
            # generator's own finally already reaped the user process.
            logger.info("Stream consumer disconnected mid-execution")
            return response
        finally:
            await gen.aclose()
        await response.write_eof()
        return response

    async def _pump_stream(gen, response, loop, t0: float) -> None:
        async for kind, payload in gen:
            if kind == "end":
                await response.write(
                    json.dumps(
                        {
                            "event": "end",
                            "stdout": payload.stdout,
                            "stderr": payload.stderr,
                            "exit_code": payload.exit_code,
                            "files": payload.files,
                            "duration_ms": (loop.time() - t0) * 1000,
                            "usage": payload.usage,
                        }
                    ).encode()
                    + b"\n"
                )
            else:
                await response.write(
                    json.dumps({"stream": kind, "data": payload}).encode()
                    + b"\n"
                )

    async def healthz(_request: web.Request) -> web.Response:
        body = {"status": "ok", "workspace": str(core.workspace)}
        if warm_error:
            # the startup accelerator warm-up failed (ExecutorCore.warmup):
            # the pod serves, but says what it could not reach
            body["warm_error"] = warm_error
        return web.json_response(body)

    app.router.add_put("/workspace/{path:.+}", upload_file)
    app.router.add_get("/workspace/{path:.+}", download_file)
    app.router.add_delete("/workspace/{path:.+}", delete_file)
    app.router.add_post("/execute", execute)
    app.router.add_post("/execute/stream", execute_stream)
    app.router.add_get("/healthz", healthz)
    return app


def core_from_env() -> ExecutorCore:
    preinstalled = load_requirements_set(
        os.environ.get("APP_REQUIREMENTS", "/requirements.txt"),
        os.environ.get("APP_REQUIREMENTS_SKIP", "/requirements-skip.txt"),
    )
    return ExecutorCore(
        workspace=os.environ.get("APP_WORKSPACE", "/workspace"),
        preinstalled=preinstalled,
        disable_dep_install=os.environ.get("APP_DISABLE_DEP_INSTALL", "") == "1",
        default_timeout_s=float(os.environ.get("APP_EXECUTION_TIMEOUT_S", "60")),
        shim_dir=os.environ.get("APP_SHIM_DIR") or None,
    )


def setup_logging() -> None:
    """Pod-side logging: request-id/trace-id on every record via the shared
    filter; APP_LOG_FORMAT=json matches the control plane's structured
    schema so both sides of a trace parse with the same pipeline."""
    handler = logging.StreamHandler()
    if os.environ.get("APP_LOG_FORMAT", "").lower() == "json":
        handler.setFormatter(JsonLogFormatter())
    else:
        handler.setFormatter(
            logging.Formatter(
                "%(asctime)s [%(levelname)s] [%(request_id)s] "
                "[%(trace_id)s] %(name)s: %(message)s"
            )
        )
    handler.addFilter(RequestIdLoggingFilter())
    root = logging.getLogger()
    root.handlers = [handler]
    root.setLevel(logging.INFO)


def main() -> None:
    setup_logging()
    core = core_from_env()
    listen = os.environ.get("APP_LISTEN_ADDR", "0.0.0.0:8000")
    host, _, port = listen.rpartition(":")
    warm_error = None
    if os.environ.get("APP_WARMUP", "") == "1":
        warm_error = asyncio.run(core.warmup())
    web.run_app(
        create_app(core, warm_error=warm_error),
        host=host or "0.0.0.0",
        port=int(port),
    )


if __name__ == "__main__":
    main()
