"""HTTP API server (aiohttp).

Same route surface and status-code contract as the reference's FastAPI app
(http_server.py:77-162): ``POST /v1/execute`` (500 on executor failure),
``POST /v1/parse-custom-tool`` (400 + ``{error_messages}`` on parse error),
``POST /v1/execute-custom-tool`` (400 + ``{stderr}`` on tool failure), plus
``GET /healthz``. FastAPI/uvicorn are not available in this environment;
aiohttp is the asyncio-native equivalent and shares the event loop with the
gRPC server exactly as the reference's uvicorn does (reference __main__.py:24-34).

Request validation errors (pydantic) return 422 like FastAPI would.

Resilience contract (docs/resilience.md): each sandbox-bound request gets a
``Deadline`` (``APP_REQUEST_DEADLINE_S``) propagated to the executor — a
blown deadline is 504. When an ``AdmissionController`` is wired in, requests
past the in-flight + queue bounds are shed as 429 with a ``Retry-After``
header instead of queueing unboundedly.

Observability contract (docs/observability.md): every ``/v1`` POST roots a
trace next to its request id (continuing an inbound ``traceparent`` when the
caller sent one); finished traces are retained in a bounded store and served
from ``GET /v1/traces`` (with ``?limit=``/``?min_duration_ms=`` filtering) +
``GET /v1/traces/{trace_id}``; ``/v1/execute`` responses carry the
``trace_id``, a per-stage ``timings_ms`` breakdown, and a per-execution
``usage`` resource-accounting block. Fleet state (the sandbox pool's
lifecycle journal) is served at ``GET /v1/fleet`` + ``GET /v1/fleet/events``,
``GET /healthz?verbose=1`` adds pool/breaker/fleet deep health (plus SLO
state when objectives are declared), and ``POST /v1/profile`` captures an
on-demand ``jax.profiler`` trace of a sandbox execution or of N
serving-engine steps. ``GET /v1/slo`` reports error-budget burn rates,
``GET /v1/debug/bundle`` is the one-call incident snapshot, and
``GET /metrics`` serves OpenMetrics-with-exemplars when the scraper's
``Accept`` header asks for it. ``GET /v1/events`` serves the flight
recorder's wide-event journal (filterable; ``?follow=1`` is a live SSE
tail), ``GET /v1/debug/tasks`` the live asyncio task inventory + loop-lag
state, and ``GET /v1/debug/pprof`` the continuous profiler's latest
collapsed-stack window. ``GET /v1/serving`` serves the serving engine's
step/KV-cache telemetry and ``GET /v1/serving/requests`` its per-request
lifecycle records (docs/observability.md "Serving observability").

Edge static analysis (docs/analysis.md): when a ``WorkloadAnalyzer`` is
wired in, every submission is parsed ONCE before any sandbox is touched —
syntax errors return a normal ``ExecuteResponse`` (exit_code=1, stderr in
the in-sandbox traceback shape) with ZERO sandbox checkouts, policy
``deny`` findings reject as 422 (a client fault, SLI-good), ``warn``
findings annotate the response, and the same pass pre-resolves deps for
the sandbox to skip its own scan.
"""

from __future__ import annotations

import asyncio
import json
import logging
import math
import textwrap
import time
from contextlib import nullcontext

import pydantic
from aiohttp import web

from bee_code_interpreter_tpu.analysis import stash_predicted_deps
from bee_code_interpreter_tpu.api import models
from bee_code_interpreter_tpu.observability import (
    PROFILE_DIR_ENV,
    REQUEST_ID_HEADER,
    FleetJournal,
    FlightRecorder,
    ProfilerUnavailable,
    Tracer,
    build_debug_bundle,
    current_trace,
    empty_slo_snapshot,
    executor_health,
    find_journal,
    inject_profile_env,
    parse_traceparent,
    profile_artifacts,
    record_sli,
    record_usage_at_edge,
    register_stream_metrics,
    register_usage_metrics,
    task_inventory,
    thread_inventory,
    unwrap_executor,
)
from bee_code_interpreter_tpu.resilience import (
    AdmissionController,
    AdmissionRejected,
    BreakerOpenError,
    Deadline,
    DeadlineExceeded,
    SandboxTransientError,
)
from bee_code_interpreter_tpu.sessions import (
    CheckpointNotFound,
    SessionLimitExceeded,
    SessionNotFound,
    streamed_events,
)
from bee_code_interpreter_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_tpu.services.custom_tool_executor import (
    CustomToolExecuteError,
    CustomToolExecutor,
    CustomToolParseError,
)
from bee_code_interpreter_tpu.tenancy import (
    TENANT_HEADER,
    bearer_token,
    build_tenants_snapshot,
    current_tenant_context,
    tenant_scope,
)
from bee_code_interpreter_tpu.utils.metrics import (
    OPENMETRICS_CONTENT_TYPE,
    PROMETHEUS_CONTENT_TYPE,
    Registry,
    accepts_openmetrics,
)
from bee_code_interpreter_tpu.utils.request_id import new_request_id

logger = logging.getLogger(__name__)


def _retry_after_header(e: AdmissionRejected | BreakerOpenError) -> dict[str, str]:
    return {"Retry-After": str(max(1, math.ceil(e.retry_after_s)))}


def create_http_server(
    code_executor: CodeExecutor,
    custom_tool_executor: CustomToolExecutor,
    metrics: Registry | None = None,
    admission: AdmissionController | None = None,
    request_deadline_s: float | None = None,
    tracer: Tracer | None = None,
    fleet: FleetJournal | None = None,
    profiler=None,  # observability.ServingProfiler for POST /v1/profile
    drain=None,  # resilience.DrainController for graceful shutdown
    supervisor=None,  # resilience.PoolSupervisor, surfaced on /v1/fleet
    slo=None,  # observability.SloEngine for GET /v1/slo + SLI recording
    debug_bundle=None,  # callable -> dict (ApplicationContext.build_debug_bundle)
    analyzer=None,  # analysis.WorkloadAnalyzer for the pre-flight code gate
    sessions=None,  # sessions.SessionManager for the /v1/sessions lease API
    recorder=None,  # observability.FlightRecorder for GET /v1/events
    loopmon=None,  # observability.LoopMonitor for GET /v1/debug/tasks
    contprof=None,  # observability.ContinuousProfiler for GET /v1/debug/pprof
    serving=None,  # observability.ServingMonitor for GET /v1/serving
    device=None,  # observability.DeviceMonitor for GET /v1/accelerator
    device_profiler=None,  # observability.DeviceProfiler for target=device
    autoscale=None,  # callable -> dict for GET /v1/autoscale (docs/autoscaling.md)
    tenancy=None,  # tenancy.TenantRegistry: identity + GET /v1/tenants
) -> web.Application:
    app = web.Application(client_max_size=1 << 30)
    metrics = metrics or Registry()
    tracer = tracer or Tracer(metrics=metrics)
    # Warm the debug bundle's `surface` section off-loop at build time:
    # the contract-lint scan is hundreds of milliseconds of synchronous
    # AST work that must not run on the event loop during the first
    # (usually mid-incident) bundle pull.
    from bee_code_interpreter_tpu.analysis import contractlint

    contractlint.warm_surface_cache()
    if recorder is None:
        # Standalone servers (tests) get their own recorder; the
        # composition root passes one already wired as a tracer sink —
        # wiring it again here would double every event.
        recorder = FlightRecorder(metrics=metrics)
        tracer.add_sink(recorder.record_trace)
    # The executor backend's own journal when it has one (pool executors
    # attach it at construction); an empty journal otherwise so /v1/fleet is
    # always mounted and answers honestly. Explicit None checks: an empty
    # journal is len()==0 and must not be replaced for being falsy.
    if fleet is None:
        fleet = find_journal(code_executor)
    if fleet is None:
        fleet = FleetJournal()
    requests_total = metrics.counter(
        "bci_http_requests_total", "HTTP requests by route and status"
    )
    request_seconds = metrics.histogram(
        "bci_http_request_seconds", "HTTP request latency by route"
    )
    deadline_exceeded_total = metrics.counter(
        "bci_deadline_exceeded_total",
        "Requests that ran out of their edge deadline",
    )
    execution_cpu_seconds, execution_peak_rss = register_usage_metrics(metrics)
    stream_ttfb_seconds, stream_chunks_total = register_stream_metrics(metrics)

    def _annotate_outcome(outcome: str, sli: bool | None) -> None:
        """Stamp the resilience ladder's verdict on the request's root span
        so the flight recorder's wide event (a tracer sink — it fires when
        the trace closes) carries the outcome and SLO classification."""
        trace = current_trace()
        if trace is not None:
            trace.root.attributes["outcome"] = outcome
            if sli is not None:
                trace.root.attributes["sli"] = "good" if sli else "bad"

    async def with_resilience(run, allow_draining: bool = False):
        """Run a sandbox-bound handler body under the edge deadline and the
        admission gate, mapping the shared shed/deadline response contract
        (docs/resilience.md) — the one place it is spelled for HTTP.
        ``run(deadline)`` returns the success response. The admission gate
        traces its own acquire as the ``admission`` stage span.

        Every request that gets past the drain check is also an SLI sample
        (docs/observability.md "SLOs"): server-side failures (5xx) burn
        availability budget, client faults (4xx) count good, and deliberate
        load management (429 shed, drain 503, client cancel) is excluded —
        ``outcome`` None means "not a sample"."""
        # Drain check BEFORE admission: a draining replica must not queue
        # new work it has promised to finish — 503 + Retry-After tells the
        # client (or the balancer) to go elsewhere, while requests already
        # in flight (tracked below) run to completion. Evacuation ops
        # (``allow_draining``: session checkpoint — the lease-handoff path,
        # docs/fleet.md) are exempt: moving existing state OUT is part of
        # finishing up, not new work.
        if drain is not None and drain.draining and not allow_draining:
            _annotate_outcome("drained", None)
            return web.json_response(
                {"detail": "Service draining; retry against another replica"},
                status=503,
                headers={"Retry-After": str(max(1, math.ceil(drain.retry_after_s)))},
            )
        deadline = Deadline.after(request_deadline_s) if request_deadline_s else None
        slo_start = time.monotonic()
        outcome: bool | None = None
        label = "cancelled"  # only a CancelledError leaves it unassigned
        # The tenant the middleware resolved (docs/tenancy.md): its quotas
        # apply at the admission gate, its SLO slice gets the sample, its
        # usage meter gets the outcome.
        tctx = current_tenant_context()
        try:
            try:
                # track() covers the admission wait too: a request already
                # granted (or queued for) a slot when the drain begins was
                # admitted past the drain check and WILL execute — teardown
                # must wait for it, not just for bodies already running.
                with drain.track() if drain is not None else nullcontext():
                    async with (
                        admission.admit(deadline, tenant=tctx)
                        if admission is not None
                        else nullcontext()
                    ):
                        response = await run(deadline)
                # bci_sli_bad: an SSE run whose terminal event reported a
                # server-side failure after the 200 status was already spent
                # (_run_sse) — the sample must burn budget like the buffered
                # path's 500 would.
                outcome = response.status < 500 and not getattr(
                    response, "bci_sli_bad", False
                )
                label = (
                    "error"
                    if not outcome
                    else ("ok" if response.status < 400 else "client_error")
                )
                return response
            except AdmissionRejected as e:
                label = "shed"
                logger.warning("Request shed: %s", e)
                # The reason in the body makes the verdict legible per
                # tenant: "tenant_quota" is YOUR quota, "queue_full" is
                # global overload (docs/tenancy.md).
                return web.json_response(
                    {
                        "detail": f"Service overloaded ({e.reason}); retry later",
                        "reason": e.reason,
                    },
                    status=429,
                    headers=_retry_after_header(e),
                )
            except DeadlineExceeded as e:
                outcome = False
                label = "deadline"
                deadline_exceeded_total.inc(transport="http")
                logger.warning("Request deadline exceeded: %s", e)
                return web.json_response({"detail": "Deadline exceeded"}, status=504)
            except BreakerOpenError as e:
                # Open breaker and no fallback configured: this is retryable
                # overload (the breaker knows when it will probe again), not a
                # server bug — 503 + Retry-After, never a generic 500.
                outcome = False
                label = "breaker_open"
                logger.warning("Request rejected by open breaker: %s", e)
                return web.json_response(
                    {"detail": "Backend temporarily unavailable; retry later"},
                    status=503,
                    headers=_retry_after_header(e),
                )
            except asyncio.CancelledError:
                raise  # client went away: not an SLI sample
            except web.HTTPException as e:
                outcome = e.status < 500  # 422 body-validation etc.
                label = "client_error" if outcome else "error"
                raise
            except BaseException:
                outcome = False  # unhandled → aiohttp's 500
                label = "error"
                raise
        finally:
            if slo is not None and outcome is not None:
                record_sli(
                    slo,
                    ok=outcome,
                    duration_s=time.monotonic() - slo_start,
                    tenant=tctx.label if tctx is not None else None,
                )
            if tctx is not None:
                # Every resolved request lands in the tenant's usage meter
                # with its outcome — sheds included, so /v1/tenants and the
                # shed counters agree by construction.
                tctx.record_request(label)
            _annotate_outcome(label, outcome)

    @web.middleware
    async def request_id_middleware(request: web.Request, handler):
        rid = new_request_id()
        # label by the *matched* route template, never the raw path: raw paths
        # are attacker-controlled (unbounded label cardinality + exposition
        # injection via percent-decoded quotes)
        # match_info is a dict subclass (empty — falsy — for static routes), so
        # test identity, not truthiness
        match_info = request.match_info
        resource = match_info.route.resource if match_info is not None else None
        route = resource.canonical if resource is not None else "unmatched"
        # Trace the sandbox-bound POSTs only: GET /metrics, /healthz and the
        # trace-inspection API itself would drown the store in self-traffic.
        traced = request.method == "POST" and route.startswith("/v1/")
        inbound = (
            parse_traceparent(request.headers.get("traceparent"))
            if traced
            else None
        )
        trace_ctx = (
            tracer.trace(
                route,
                trace_id=inbound[0] if inbound else None,
                parent_span_id=inbound[1] if inbound else None,
                request_id=rid,
            )
            if traced
            else nullcontext()
        )
        # Tenant identity resolves HERE — once, for every route — into the
        # ambient context every downstream layer reads (docs/tenancy.md).
        # tenant_scope(None) when no registry is wired still clears any
        # context a previous request on this keep-alive connection left.
        tctx = None
        if tenancy is not None:
            tctx = tenancy.resolve(
                request.headers.get(TENANT_HEADER),
                bearer_token(request.headers.get("Authorization")),
            )
            if admission is not None and tctx.retry_budget is None:
                tctx.retry_budget = admission.tenant_retry_budget(tctx)
        with tenant_scope(tctx):
            with trace_ctx:
                if traced and tctx is not None:
                    trace = current_trace()
                    if trace is not None:
                        # The root-span attribute the wide event lifts into
                        # its first-class `tenant` field.
                        trace.root.attributes["tenant"] = tctx.label
                with request_seconds.time(route=route):
                    try:
                        response = await handler(request)
                    except web.HTTPException as e:
                        requests_total.inc(route=route, status=str(e.status))
                        e.headers.setdefault(REQUEST_ID_HEADER, rid)
                        raise
                    except Exception:
                        requests_total.inc(route=route, status="500")
                        raise
        requests_total.inc(route=route, status=str(response.status))
        response.headers.setdefault(REQUEST_ID_HEADER, rid)
        return response

    app.middlewares.append(request_id_middleware)

    async def parse_body(request: web.Request, model: type[pydantic.BaseModel]):
        try:
            # pydantic v2 handles malformed JSON itself (json_invalid → 422).
            return model.model_validate_json(await request.read())
        except pydantic.ValidationError as e:
            raise web.HTTPUnprocessableEntity(
                text=e.json(), content_type="application/json"
            ) from e

    def _truthy_query(request: web.Request, name: str) -> bool:
        return request.query.get(name, "").lower() in ("1", "true", "yes", "on")

    def _stream_backend():
        """The pool/local backend implementing ``execute_stream`` behind the
        resilience fronts. Streaming deliberately bypasses retry/replay/
        hedging: chunks already delivered to a client cannot be
        un-delivered, so a mid-stream failure is a terminal error event,
        never a silent re-run."""
        backend = unwrap_executor(code_executor)
        return backend if hasattr(backend, "execute_stream") else None

    async def _sse_prepare(request: web.Request) -> web.StreamResponse:
        response = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-store",
                "X-Accel-Buffering": "no",  # proxies must not re-buffer SSE
            }
        )
        response.enable_chunked_encoding()
        await response.prepare(request)
        return response

    async def _sse_event(response, event: str, data: dict) -> None:
        await response.write(
            f"event: {event}\ndata: {json.dumps(data)}\n\n".encode()
        )

    async def _run_sse(request, verdict, execute_call, envelope):
        """Drive one streaming execution as SSE (docs/sessions.md
        "Streaming wire format"): ``stdout``/``stderr`` events per chunk,
        exactly one terminal ``result`` (the usual envelope, trace_id
        included) or ``error`` event. Once the stream is prepared the HTTP
        status is spent, so failures are in-band terminal events."""
        start = time.monotonic()
        chunks = 0
        first_chunk_s: float | None = None

        def _annotate_stream() -> None:
            """Stream context onto the root span (→ the wide event) and the
            production streaming metrics the bench numbers graduated into."""
            stream_chunks_total.inc(chunks, transport="http")
            trace = current_trace()
            if trace is not None:
                trace.root.attributes["stream.chunks"] = str(chunks)
                if first_chunk_s is not None:
                    trace.root.attributes["stream.ttfb_ms"] = (
                        f"{first_chunk_s * 1000:.3f}"
                    )

        response = await _sse_prepare(request)
        # finally, not a tail call: a client that vanishes mid-stream (write
        # raises / handler cancelled) must still count its delivered chunks
        # and leave stream context on the wide event — abnormal streams are
        # exactly the ones an operator queries for.
        try:
            if verdict is not None and verdict.syntax_error is not None:
                # Fail-fast mirrors the buffered path: zero sandbox
                # checkouts, the terminal event IS the whole stream.
                trace = current_trace()
                await _sse_event(
                    response,
                    "result",
                    models.ExecuteResponse(
                        stdout="",
                        stderr=verdict.syntax_error,
                        exit_code=1,
                        files={},
                        trace_id=trace.trace_id if trace is not None else None,
                        timings_ms=(
                            trace.stage_ms() if trace is not None else None
                        ),
                    ).model_dump(),
                )
                await response.write_eof()
                return response
            async for item in streamed_events(execute_call):
                if item.get("event") == "error":
                    error = item["error"]
                    if isinstance(error, asyncio.CancelledError):
                        raise error  # our own unwind (client gone); don't mask it
                    logger.warning("Streaming execution failed: %r", error)
                    if isinstance(error, DeadlineExceeded):
                        detail = "Deadline exceeded"
                    elif isinstance(error, SessionNotFound):
                        detail = str(error)
                    else:
                        detail = "Execution failed"
                    if not isinstance(error, SessionNotFound):
                        # The 200 status was spent at prepare time, but a
                        # mid-stream server failure must still burn
                        # availability budget — the gRPC twin (ExecuteStream)
                        # samples the identical failure bad, and the
                        # transports must agree. SessionNotFound is the
                        # client's fault (the buffered path's 404), so it
                        # stays good.
                        response.bci_sli_bad = True
                    await _sse_event(response, "error", {"detail": detail})
                elif item.get("event") == "result":
                    await _sse_event(
                        response, "result", envelope(item["result"])
                    )
                else:
                    if chunks == 0:
                        first_chunk_s = time.monotonic() - start
                        stream_ttfb_seconds.observe(
                            first_chunk_s, transport="http"
                        )
                    chunks += 1
                    await _sse_event(
                        response, item["stream"], {"text": item["data"]}
                    )
            await response.write_eof()
            return response
        finally:
            _annotate_stream()

    async def execute(request: web.Request) -> web.Response:
        # Admission runs BEFORE the body is read: a shed request must cost a
        # queue check, not a (up to client_max_size) body read + pydantic
        # parse. The deadline covers the body read too.
        async def run(deadline):
            req = await parse_body(request, models.ExecuteRequest)
            # Clear any prediction left by a previous request: aiohttp serves
            # sequential keep-alive requests on ONE connection task, so the
            # contextvar would otherwise leak across requests.
            stash_predicted_deps(None)
            streaming = _truthy_query(request, "stream")
            verdict = (
                analyzer.analyze(req.source_code)
                if analyzer is not None
                else None
            )
            if verdict is not None:
                if verdict.syntax_error is not None and not streaming:
                    # Fail-fast: the sandbox would have died at parse with
                    # this exact stderr shape — answer it from the edge
                    # without a pool checkout (the fleet journal stays
                    # untouched; timings_ms carries only `analysis`).
                    trace = current_trace()
                    return web.json_response(
                        models.ExecuteResponse(
                            stdout="",
                            stderr=verdict.syntax_error,
                            exit_code=1,
                            files={},
                            trace_id=(
                                trace.trace_id if trace is not None else None
                            ),
                            timings_ms=(
                                trace.stage_ms() if trace is not None else None
                            ),
                        ).model_dump()
                    )
                if verdict.denials:
                    logger.warning(
                        "Request denied by policy: %s", verdict.denial_detail()
                    )
                    return web.json_response(
                        {
                            "detail": "Denied by execution policy",
                            "violations": [
                                f.to_dict() for f in verdict.denials
                            ],
                        },
                        status=422,
                    )
                # The edge already scanned: ship the prediction with the
                # data-plane call so the pod skips its own scan.
                stash_predicted_deps(verdict.predicted_deps)
            # Cost-aware admission (opt-in, docs/analysis.md "Cost
            # classes"): heavy-classified work passes the bounded heavy
            # lane; a shed here surfaces as the ordinary 429 contract.
            heavy_lane = (
                admission.heavy_lane(verdict.cost_class)
                if admission is not None and verdict is not None
                else nullcontext()
            )
            async with heavy_lane:
                if streaming:
                    backend = _stream_backend()
                    if backend is None:
                        return web.json_response(
                            {"detail": "this backend cannot stream output"},
                            status=501,
                        )

                    def envelope(result) -> dict:
                        trace = current_trace()
                        record_usage_at_edge(
                            result.usage,
                            trace,
                            execution_cpu_seconds,
                            execution_peak_rss,
                        )
                        return models.ExecuteResponse(
                            **result.model_dump(),
                            trace_id=trace.trace_id if trace is not None else None,
                            timings_ms=(
                                trace.stage_ms() if trace is not None else None
                            ),
                            analysis=(
                                verdict.annotation() if verdict is not None else None
                            ),
                        ).model_dump()

                    return await _run_sse(
                        request,
                        verdict,
                        lambda on_event: backend.execute_stream(
                            req.source_code,
                            files=req.files,
                            env=req.env,
                            timeout_s=req.timeout,
                            on_event=on_event,
                            deadline=deadline,
                        ),
                        envelope,
                    )
                logger.info("Executing code: %s", req.source_code)
                try:
                    result = await code_executor.execute(
                        source_code=req.source_code,
                        files=req.files,
                        env=req.env,
                        timeout_s=req.timeout,
                        deadline=deadline,
                    )
                except (DeadlineExceeded, BreakerOpenError):
                    raise  # handled by the shared resilience contract (504/503)
                except Exception:
                    logger.exception("Execution failed")
                    return web.json_response(
                        {"detail": "Execution failed"}, status=500
                    )
                logger.info("Execution result: exit_code=%s", result.exit_code)
                # Per-stage timing breakdown off the request's own trace: the
                # stage spans have all finished by now (the root closes with
                # the middleware), so agents/benchmarks can self-report where
                # the time went without a second round-trip to /v1/traces.
                trace = current_trace()
                # Execution-cost accounting lands at the edge: histograms +
                # usage.* attributes on the root span, mirroring the response.
                record_usage_at_edge(
                    result.usage, trace, execution_cpu_seconds, execution_peak_rss
                )
                return web.json_response(
                    models.ExecuteResponse(
                        **result.model_dump(),
                        trace_id=trace.trace_id if trace is not None else None,
                        timings_ms=trace.stage_ms() if trace is not None else None,
                        analysis=(
                            verdict.annotation() if verdict is not None else None
                        ),
                    ).model_dump()
                )

        return await with_resilience(run)

    async def profile(request: web.Request) -> web.Response:
        """On-demand jax.profiler capture (docs/observability.md): drill
        into a slow request found via /v1/traces without redeploying."""

        async def run(deadline):
            req = await parse_body(request, models.ProfileRequest)
            # Profiled executions are not analyzed; clear any prediction a
            # previous request on this connection task stashed so the pod
            # scans THIS source itself.
            stash_predicted_deps(None)
            if req.target == "serving":
                # 501 both when no profiler was wired AND when one exists
                # but its stepper has no engine attached yet (the
                # composition root wires the profiler unconditionally; the
                # engine arrives via ApplicationContext.attach_serving_engine)
                if profiler is None or not getattr(
                    profiler, "available", True
                ):
                    return web.json_response(
                        {"detail": "no serving engine attached to /v1/profile"},
                        status=501,
                    )
                try:
                    # Off-loop: a capture steps the batcher N times, which
                    # is device-bound work the event loop must not eat.
                    captured = await asyncio.to_thread(
                        profiler.capture, req.steps
                    )
                except ProfilerUnavailable as e:
                    return web.json_response({"detail": str(e)}, status=503)
                return web.json_response({"target": "serving", **captured})

            if req.target == "device":
                # Raw device-runtime capture (docs/observability.md
                # "Accelerator observability"): the attached engine's
                # steps under its own profiler trace. 501 with the reason
                # when there is nothing to trace through (no profiler
                # wired, no in-process engine — this process then does
                # not hold the device — or start_trace rejected by the
                # backend); 503 only for the transient
                # capture-already-running case.
                if device_profiler is None or not getattr(
                    device_profiler, "available", True
                ):
                    return web.json_response(
                        {
                            "detail": "device profiling unavailable: no "
                            "in-process engine attached (the device "
                            "belongs to the process that runs one)"
                        },
                        status=501,
                    )
                try:
                    captured = await asyncio.to_thread(
                        device_profiler.capture, req.steps
                    )
                except ProfilerUnavailable as e:
                    busy = device_profiler.capturing
                    return web.json_response(
                        {"detail": str(e)}, status=503 if busy else 501
                    )
                return web.json_response({"target": "device", **captured})

            if not req.source_code:
                return web.json_response(
                    {"detail": "source_code is required for target=sandbox"},
                    status=422,
                )
            env = inject_profile_env(req.env)
            profile_dir = env[PROFILE_DIR_ENV]
            try:
                result = await code_executor.execute(
                    source_code=req.source_code,
                    files=req.files,
                    env=env,
                    timeout_s=req.timeout,
                    deadline=deadline,
                )
            except (DeadlineExceeded, BreakerOpenError):
                raise  # shared resilience contract (504/503)
            except Exception:
                logger.exception("Profiled execution failed")
                return web.json_response({"detail": "Execution failed"}, status=500)
            trace = current_trace()
            record_usage_at_edge(
                result.usage, trace, execution_cpu_seconds, execution_peak_rss
            )
            return web.json_response(
                {
                    "target": "sandbox",
                    **models.ExecuteResponse(
                        **result.model_dump(),
                        trace_id=trace.trace_id if trace is not None else None,
                        timings_ms=(
                            trace.stage_ms() if trace is not None else None
                        ),
                    ).model_dump(),
                    "profile_dir": profile_dir,
                    "profile_files": profile_artifacts(
                        result.files, profile_dir
                    ),
                }
            )

        return await with_resilience(run)

    async def parse_custom_tool(request: web.Request) -> web.Response:
        req = await parse_body(request, models.ParseCustomToolRequest)
        try:
            tool = custom_tool_executor.parse(req.tool_source_code)
        except CustomToolParseError as e:
            return web.json_response({"error_messages": e.error_messages}, status=400)
        return web.json_response(
            models.ParseCustomToolResponse(
                tool_name=tool.name,
                tool_input_schema_json=json.dumps(tool.input_schema),
                tool_description=tool.description,
            ).model_dump()
        )

    async def execute_custom_tool(request: web.Request) -> web.Response:
        async def run(deadline):
            req = await parse_body(request, models.ExecuteCustomToolRequest)
            stash_predicted_deps(None)  # see execute(): per-request reset
            if analyzer is not None:
                # Tool sources get the policy half only, analyzed DEDENTED —
                # the same preprocessing the parser applies, so a uniformly
                # indented tool can't slip past the policy as a "syntax
                # error". A real syntax error keeps the parser's 400 +
                # error_messages contract (fail-fast skipped), and no dep
                # prediction is stashed: the sandbox runs the generated
                # wrapper (whose own imports, e.g. pydantic, the tool source
                # doesn't mention), so the in-pod scan must still run.
                verdict = analyzer.analyze(
                    textwrap.dedent(req.tool_source_code)
                )
                if verdict.syntax_error is None and verdict.denials:
                    logger.warning(
                        "Tool denied by policy: %s", verdict.denial_detail()
                    )
                    return web.json_response(
                        {
                            "detail": "Denied by execution policy",
                            "violations": [
                                f.to_dict() for f in verdict.denials
                            ],
                        },
                        status=422,
                    )
            try:
                output = await custom_tool_executor.execute(
                    tool_source_code=req.tool_source_code,
                    tool_input_json=req.tool_input_json,
                    env=req.env,
                    deadline=deadline,
                )
            except CustomToolParseError as e:
                return web.json_response(
                    {"error_messages": e.error_messages}, status=400
                )
            except CustomToolExecuteError as e:
                return web.json_response({"stderr": e.stderr}, status=400)
            except (DeadlineExceeded, BreakerOpenError):
                raise  # shared resilience contract (504/503)
            except Exception:
                # Without this arm a raw sandbox failure escaped as
                # aiohttp's default text/plain 500 (no detail, no JSON)
                # while /v1/execute answered a JSON 500 — and the gRPC
                # twin aborts INTERNAL "execution failed".
                logger.exception("Custom tool execution failed")
                return web.json_response(
                    {"detail": "Execution failed"}, status=500
                )
            return web.json_response(
                models.ExecuteCustomToolResponse(
                    tool_output_json=json.dumps(output)
                ).model_dump()
            )

        return await with_resilience(run)

    # ------------------------------------------------------------- sessions

    def _sessions_unwired() -> web.Response:
        return web.json_response(
            {"detail": "no session manager wired into this server"}, status=501
        )

    def _session_trace_attr(session_id: str) -> None:
        """Thread the session id through tracing: a ``session`` attribute on
        the request's root span, visible in /v1/traces and the OTLP export."""
        trace = current_trace()
        if trace is not None:
            trace.root.attributes["session"] = session_id

    def _session_execute_envelope(
        session, outcome, verdict=None
    ) -> dict:
        trace = current_trace()
        record_usage_at_edge(
            outcome.usage, trace, execution_cpu_seconds, execution_peak_rss
        )
        return models.SessionExecuteResponse(
            stdout=outcome.stdout,
            stderr=outcome.stderr,
            exit_code=outcome.exit_code,
            changed_paths=outcome.changed_paths,
            session_id=session.session_id,
            execution=session.executions,
            expires_at=session.expires_unix,
            trace_id=trace.trace_id if trace is not None else None,
            timings_ms=trace.stage_ms() if trace is not None else None,
            usage=outcome.usage,
            analysis=verdict.annotation() if verdict is not None else None,
        ).model_dump()

    async def session_create(request: web.Request) -> web.Response:
        if sessions is None:
            return _sessions_unwired()

        async def run(deadline):
            req = await parse_body(request, models.SessionCreateRequest)
            stash_predicted_deps(None)
            try:
                session = await sessions.create(
                    files=req.files,
                    ttl_s=req.ttl_s,
                    idle_s=req.idle_s,
                    deadline=deadline,
                )
            except SessionLimitExceeded as e:
                return web.json_response(
                    {"detail": str(e)},
                    status=429,
                    headers={
                        "Retry-After": str(max(1, math.ceil(e.retry_after_s)))
                    },
                )
            except (DeadlineExceeded, BreakerOpenError):
                raise  # shared resilience contract (504/503)
            except Exception:
                logger.exception("Session create failed")
                return web.json_response(
                    {"detail": "Session create failed"}, status=500
                )
            _session_trace_attr(session.session_id)
            return web.json_response(
                models.SessionCreateResponse(
                    session_id=session.session_id,
                    expires_at=session.expires_unix,
                    ttl_s=session.ttl_s,
                    idle_timeout_s=session.idle_s,
                    sandbox=session.lease.name,
                ).model_dump()
            )

        return await with_resilience(run)

    async def session_execute(request: web.Request) -> web.Response:
        if sessions is None:
            return _sessions_unwired()
        session_id = request.match_info["session_id"]

        async def run(deadline):
            req = await parse_body(request, models.SessionExecuteRequest)
            stash_predicted_deps(None)
            _session_trace_attr(session_id)
            streaming = _truthy_query(request, "stream")
            # Admission/deadline/analysis/SLO apply per-execute exactly as
            # on the stateless path (docs/sessions.md): the analyzer gate
            # runs BEFORE the leased sandbox is touched.
            verdict = (
                analyzer.analyze(req.source_code)
                if analyzer is not None
                else None
            )
            try:
                session = sessions.get(session_id)
            except SessionNotFound as e:
                return web.json_response({"detail": str(e)}, status=404)
            if verdict is not None:
                if verdict.syntax_error is not None and not streaming:
                    # Fail-fast without touching the lease (it stays warm,
                    # its idle clock untouched by a doomed submission).
                    return web.json_response(
                        _session_execute_envelope(
                            session,
                            _syntax_outcome(verdict.syntax_error),
                        )
                    )
                if verdict.denials:
                    logger.warning(
                        "Session execute denied by policy: %s",
                        verdict.denial_detail(),
                    )
                    return web.json_response(
                        {
                            "detail": "Denied by execution policy",
                            "violations": [
                                f.to_dict() for f in verdict.denials
                            ],
                        },
                        status=422,
                    )
                stash_predicted_deps(verdict.predicted_deps)
            if streaming:
                return await _run_sse(
                    request,
                    verdict,
                    lambda on_event: sessions.execute(
                        session_id,
                        req.source_code,
                        files=req.files,
                        env=req.env,
                        timeout_s=req.timeout,
                        deadline=deadline,
                        on_event=on_event,
                    ),
                    lambda pair: _session_execute_envelope(
                        pair[0], pair[1], verdict
                    ),
                )
            try:
                session, outcome = await sessions.execute(
                    session_id,
                    req.source_code,
                    files=req.files,
                    env=req.env,
                    timeout_s=req.timeout,
                    deadline=deadline,
                )
            except SessionNotFound as e:
                return web.json_response({"detail": str(e)}, status=404)
            except (DeadlineExceeded, BreakerOpenError):
                raise  # shared resilience contract (504/503)
            except SandboxTransientError:
                logger.exception("Leased sandbox died mid-execute")
                return web.json_response(
                    {"detail": "Session sandbox died; lease ended"},
                    status=500,
                )
            except Exception:
                logger.exception("Session execution failed")
                return web.json_response(
                    {"detail": "Execution failed"}, status=500
                )
            return web.json_response(
                _session_execute_envelope(session, outcome, verdict)
            )

        return await with_resilience(run)

    def _syntax_outcome(stderr: str):
        from bee_code_interpreter_tpu.sessions import LeaseOutcome

        return LeaseOutcome(stdout="", stderr=stderr, exit_code=1)

    async def session_checkpoint(request: web.Request) -> web.Response:
        if sessions is None:
            return _sessions_unwired()
        session_id = request.match_info["session_id"]

        async def run(deadline):
            stash_predicted_deps(None)
            _session_trace_attr(session_id)
            try:
                session, checkpoint = await sessions.checkpoint(
                    session_id, deadline=deadline
                )
            except SessionNotFound as e:
                return web.json_response({"detail": str(e)}, status=404)
            except (DeadlineExceeded, BreakerOpenError):
                raise
            except Exception:
                logger.exception("Session checkpoint failed")
                return web.json_response(
                    {"detail": "Checkpoint failed"}, status=500
                )
            return web.json_response(
                models.SessionCheckpointResponse(
                    session_id=session.session_id,
                    checkpoint_id=checkpoint.checkpoint_id,
                    files=checkpoint.files,
                ).model_dump()
            )

        # allow_draining: a fleet router evacuating this replica's leases
        # checkpoints them THROUGH the drain window (docs/fleet.md).
        return await with_resilience(run, allow_draining=True)

    async def session_rollback(request: web.Request) -> web.Response:
        if sessions is None:
            return _sessions_unwired()
        session_id = request.match_info["session_id"]

        async def run(deadline):
            req = await parse_body(request, models.SessionRollbackRequest)
            stash_predicted_deps(None)
            _session_trace_attr(session_id)
            try:
                session, checkpoint = await sessions.rollback(
                    session_id, req.checkpoint_id, deadline=deadline
                )
            except (SessionNotFound, CheckpointNotFound) as e:
                return web.json_response({"detail": str(e)}, status=404)
            except (DeadlineExceeded, BreakerOpenError):
                raise
            except Exception:
                logger.exception("Session rollback failed")
                return web.json_response(
                    {"detail": "Rollback failed"}, status=500
                )
            return web.json_response(
                models.SessionCheckpointResponse(
                    session_id=session.session_id,
                    checkpoint_id=checkpoint.checkpoint_id,
                    files=checkpoint.files,
                ).model_dump()
            )

        return await with_resilience(run)

    async def session_delete(request: web.Request) -> web.Response:
        if sessions is None:
            return _sessions_unwired()
        session_id = request.match_info["session_id"]
        try:
            session = await sessions.release(session_id)
        except SessionNotFound as e:
            return web.json_response({"detail": str(e)}, status=404)
        return web.json_response(
            {
                "session_id": session.session_id,
                "released": True,
                "executions": session.executions,
            }
        )

    async def session_list(_request: web.Request) -> web.Response:
        if sessions is None:
            return _sessions_unwired()
        return web.json_response(sessions.snapshot())

    async def healthz(request: web.Request) -> web.Response:
        # "draining" is a distinct liveness answer (still HTTP 200: the
        # process is healthy, just finishing up) so preStop hooks and
        # health_check.py can tell a draining replica from a dead one.
        draining = drain is not None and drain.draining
        body: dict = {"status": "draining" if draining else "ok"}
        # explicit truthy values only: ?verbose=0 / =false must stay terse
        if request.query.get("verbose", "").lower() in ("1", "true", "yes", "on"):
            # Deep health: pool occupancy, breaker states, fleet aggregates
            # — the "why is it unhealthy" view a bare 200 can't carry.
            body.update(executor_health(code_executor))
            if draining:
                body["drain_inflight"] = drain.in_flight
            if supervisor is not None:
                body["supervisor"] = supervisor.snapshot()
            snapshot = fleet.snapshot()
            body["fleet"] = {
                "live": snapshot["live"],
                "by_state": snapshot["by_state"],
                "utilization": snapshot["utilization"],
                "executions_total": snapshot["executions_total"],
            }
            if slo is not None and slo.objectives:
                # Budget exhaustion is a *health* fact: health_check.py's
                # --verbose warning exit keys off fast_burn_alerting here.
                body["slo"] = slo.snapshot()
            if loopmon is not None:
                # Loop health next to pool health: a stalled loop makes
                # every other number here lie by omission.
                body["loop"] = loopmon.snapshot()
        return web.json_response(body)

    async def metrics_endpoint(request: web.Request) -> web.Response:
        # Content negotiation: OpenMetrics (exemplars + `# EOF`) when the
        # scraper asks for it (q-values honored), the classic Prometheus
        # text format (version parameter included, so scrapers pick the
        # parser) by default.
        openmetrics = accepts_openmetrics(request.headers.get("Accept", ""))
        return web.Response(
            body=metrics.expose(openmetrics=openmetrics).encode("utf-8"),
            headers={
                "Content-Type": (
                    OPENMETRICS_CONTENT_TYPE
                    if openmetrics
                    else PROMETHEUS_CONTENT_TYPE
                )
            },
        )

    async def slo_endpoint(request: web.Request) -> web.Response:
        if slo is None:
            return web.json_response(empty_slo_snapshot())
        tenant = request.query.get("tenant")
        if tenant is not None:
            # One tenant's SLO slice (docs/tenancy.md "SLO slices").
            return web.json_response(slo.tenant_snapshot(tenant))
        return web.json_response(slo.snapshot())

    async def tenants_endpoint(_request: web.Request) -> web.Response:
        """Per-tenant isolation + billing view (docs/tenancy.md): declared
        quotas, live admission state, usage metering, SLO-slice burn, and
        session counts — the blast-radius accounting surface."""
        if tenancy is None:
            return web.json_response(
                {"detail": "no tenant registry wired into this server"},
                status=501,
            )
        return web.json_response(
            build_tenants_snapshot(
                tenancy, admission=admission, slo=slo, sessions=sessions
            )
        )

    async def autoscale_endpoint(_request: web.Request) -> web.Response:
        """Capacity observability (docs/autoscaling.md): the demand
        snapshot, the forecast, the current/target pool size, and the
        bounded scaling-decision log with reasons."""
        if autoscale is None:
            return web.json_response(
                {"detail": "no capacity tracker wired into this server"},
                status=501,
            )
        return web.json_response(autoscale())

    async def debug_bundle_endpoint(_request: web.Request) -> web.Response:
        # One-call incident snapshot (docs/observability.md "Debug bundle").
        # The composition root's builder when wired; otherwise assembled
        # from what this server was handed (standalone/test apps).
        bundle = (
            debug_bundle()
            if debug_bundle is not None
            else build_debug_bundle(
                tracer=tracer,
                fleet=fleet,
                slo=slo,
                metrics=metrics,
                executor=code_executor,
                supervisor=supervisor,
                drain=drain,
                recorder=recorder,
                loopmon=loopmon,
                contprof=contprof,
                serving=serving,
                autoscale=autoscale,
                tenancy=tenancy,
            )
        )
        return web.json_response(bundle)

    async def list_traces(request: web.Request) -> web.Response:
        # ?limit=N caps the response (newest first); ?min_duration_ms=X
        # keeps only the slow outliers — the query an operator actually
        # runs, instead of dumping the whole ring every time.
        try:
            limit = (
                int(request.query["limit"])
                if "limit" in request.query
                else None
            )
            min_duration_ms = (
                float(request.query["min_duration_ms"])
                if "min_duration_ms" in request.query
                else None
            )
        except ValueError:
            return web.json_response(
                {"detail": "limit and min_duration_ms must be numeric"},
                status=400,
            )
        if limit is not None and limit < 0:
            return web.json_response(
                {"detail": "limit must be >= 0"}, status=400
            )
        traces = tracer.store.traces()
        if min_duration_ms is not None:
            traces = [
                t for t in traces if t.duration_s * 1000.0 >= min_duration_ms
            ]
        if limit is not None:
            traces = traces[:limit]
        return web.json_response({"traces": [t.summary() for t in traces]})

    async def get_trace(request: web.Request) -> web.Response:
        trace = tracer.store.get(request.match_info["trace_id"])
        if trace is None:
            return web.json_response(
                {"detail": "unknown or evicted trace"}, status=404
            )
        return web.json_response(trace.to_dict())

    async def list_events(request: web.Request) -> web.StreamResponse:
        """The flight recorder's wide-event journal (docs/observability.md
        "Flight recorder"): filterable snapshot by default, a live SSE tail
        with ``?follow=1`` (same filters; ``backlog=N`` replays the last N
        matching events first)."""
        from bee_code_interpreter_tpu.observability import event_matches

        query = request.query
        try:
            limit = int(query["limit"]) if "limit" in query else None
            backlog = int(query.get("backlog", "0"))
            min_duration_ms = (
                float(query["min_duration_ms"])
                if "min_duration_ms" in query
                else None
            )
            since = float(query["since"]) if "since" in query else None
        except ValueError:
            return web.json_response(
                {
                    "detail": "limit, backlog, min_duration_ms and since "
                    "must be numeric"
                },
                status=400,
            )
        if (limit is not None and limit < 0) or backlog < 0:
            return web.json_response(
                {"detail": "limit and backlog must be >= 0"}, status=400
            )
        filters = {
            "kind": query.get("kind"),
            "outcome": query.get("outcome"),
            "session": query.get("session"),
            "tenant": query.get("tenant"),
            "min_duration_ms": min_duration_ms,
            "since": since,
        }
        if not _truthy_query(request, "follow"):
            return web.json_response(
                {"events": recorder.events(limit=limit, **filters)}
            )
        response = await _sse_prepare(request)
        # Subscribe BEFORE replaying the backlog: an event recorded between
        # the two is delivered (possibly twice at the seam — consumers
        # dedupe on `seq`), never lost.
        queue = recorder.subscribe()
        try:
            for event in reversed(recorder.events(limit=backlog, **filters)):
                await _sse_event(response, "wide_event", event)
            while True:
                try:
                    event = await asyncio.wait_for(queue.get(), timeout=15.0)
                except asyncio.TimeoutError:
                    # SSE comment as keep-alive so idle tails survive
                    # proxies with read timeouts.
                    await response.write(b": keep-alive\n\n")
                    continue
                if event_matches(event, **filters):
                    await _sse_event(response, "wide_event", event)
        except (ConnectionResetError, ConnectionAbortedError):
            return response  # tail client went away: a normal ending
        finally:
            recorder.unsubscribe(queue)

    async def debug_tasks(_request: web.Request) -> web.Response:
        """Live task/thread inventory + the loop monitor's lag state (and
        its last captured stall, stacks included)."""
        body = task_inventory()
        body["threads"] = thread_inventory()
        if loopmon is not None:
            body["monitor"] = loopmon.snapshot()
        return web.json_response(body)

    async def debug_pprof(request: web.Request) -> web.Response:
        """The continuous profiler's latest window: collapsed-stack text
        (feed it straight to flamegraph tooling) or ``?format=json`` for
        the structured view incl. the trace ids active during sampling."""
        if contprof is None:
            return web.json_response(
                {"detail": "no continuous profiler wired into this server"},
                status=501,
            )
        if request.query.get("format", "").lower() == "json":
            return web.json_response(contprof.snapshot())
        return web.Response(
            text=contprof.collapsed() + "\n", content_type="text/plain"
        )

    async def serving_snapshot(request: web.Request) -> web.Response:
        """The serving engine's deep-observability view (docs/observability.md
        "Serving observability"): batcher/queue aggregates, KV-cache
        telemetry, lifetime totals, and the last ``?steps=N`` step records
        (default 32). 501 when no ServingMonitor is wired (standalone
        servers); with one wired but no engine attached the body answers
        honestly (``attached: false``)."""
        if serving is None:
            return web.json_response(
                {"detail": "no serving monitor wired into this server"},
                status=501,
            )
        try:
            steps = int(request.query.get("steps", "32"))
        except ValueError:
            return web.json_response(
                {"detail": "steps must be an integer"}, status=400
            )
        if steps < 0:
            return web.json_response(
                {"detail": "steps must be >= 0"}, status=400
            )
        return web.json_response(serving.snapshot(steps=steps))

    async def serving_requests(request: web.Request) -> web.Response:
        """Per-request lifecycle records, newest first, with filters:
        ``outcome`` (ok/error/cancelled/preempted), ``finish`` (the batcher
        done reason), ``adapter``, ``active`` (1/0), ``min_duration_ms``,
        ``limit``."""
        if serving is None:
            return web.json_response(
                {"detail": "no serving monitor wired into this server"},
                status=501,
            )
        query = request.query
        try:
            limit = int(query["limit"]) if "limit" in query else None
            adapter = int(query["adapter"]) if "adapter" in query else None
            min_duration_ms = (
                float(query["min_duration_ms"])
                if "min_duration_ms" in query
                else None
            )
        except ValueError:
            return web.json_response(
                {
                    "detail": "limit, adapter and min_duration_ms must be "
                    "numeric"
                },
                status=400,
            )
        if limit is not None and limit < 0:
            return web.json_response(
                {"detail": "limit must be >= 0"}, status=400
            )
        active = (
            _truthy_query(request, "active") if "active" in query else None
        )
        return web.json_response(
            {
                "requests": serving.requests(
                    outcome=query.get("outcome"),
                    finish=query.get("finish"),
                    adapter=adapter,
                    active=active,
                    min_duration_ms=min_duration_ms,
                    limit=limit,
                )
            }
        )

    async def accelerator_snapshot(request: web.Request) -> web.Response:
        """The accelerator observability view (docs/observability.md
        "Accelerator observability"): compile/retrace totals + per-function
        signature sets, the latest device-memory sample (estimated on
        CPU-only runtimes), per-mesh-shape step timing, and KV-pool
        occupancy. ``?recent=N`` bounds the compile-record tail (default
        16). 501 when no DeviceMonitor is wired (standalone servers); with
        one wired but no engine attached the body answers honestly
        (``attached: false``)."""
        if device is None:
            return web.json_response(
                {"detail": "no device monitor wired into this server"},
                status=501,
            )
        try:
            recent = int(request.query.get("recent", "16"))
        except ValueError:
            return web.json_response(
                {"detail": "recent must be an integer"}, status=400
            )
        if recent < 0:
            return web.json_response(
                {"detail": "recent must be >= 0"}, status=400
            )
        return web.json_response(device.snapshot(recent=recent))

    async def fleet_snapshot(_request: web.Request) -> web.Response:
        snap = fleet.snapshot()
        # Supervisor + drain state ride on the fleet view: "is anything
        # healing or draining right now" belongs next to "what is the pool
        # doing" (scripts/fleet-top.py renders both).
        if supervisor is not None:
            snap["supervisor"] = supervisor.snapshot()
        snap["draining"] = bool(drain is not None and drain.draining)
        if sessions is not None:
            # Lease table next to the pool view: leased pods in `pods`
            # already carry owner session + lease age; this is the summary
            # (active/max, how leases have been ending).
            snap["sessions"] = sessions.snapshot()
        if analyzer is not None:
            # The analyzer's running cost-class mix (docs/analysis.md "Cost
            # classes"): exported here so the fleet router's refresh loop
            # sees what KIND of work each replica has been absorbing, not
            # just how much.
            snap["cost_classes"] = dict(analyzer.cost_class_counts)
        if tenancy is not None:
            # Tenant mix (docs/tenancy.md): per-tenant request totals, so
            # a fleet router can place by WHO is sending, not just how
            # much is arriving.
            snap["tenants"] = tenancy.mix()
        if device is not None:
            # Accelerator summary (docs/observability.md "Accelerator
            # observability"): compile/retrace totals + HBM headroom, so
            # a fleet router can steer load away from replicas that are
            # retracing or memory-tight.
            snap["accelerator"] = device.fleet_summary()
        return web.json_response(snap)

    async def fleet_events(request: web.Request) -> web.Response:
        try:
            limit = int(request.query.get("limit", "100"))
        except ValueError:
            return web.json_response(
                {"detail": "limit must be an integer"}, status=400
            )
        if limit < 0:
            return web.json_response(
                {"detail": "limit must be >= 0"}, status=400
            )
        return web.json_response({"events": fleet.events(limit=limit)})

    app.router.add_post("/v1/execute", execute)
    app.router.add_post("/v1/sessions", session_create)
    app.router.add_get("/v1/sessions", session_list)
    app.router.add_post("/v1/sessions/{session_id}/execute", session_execute)
    app.router.add_post("/v1/sessions/{session_id}/checkpoint", session_checkpoint)
    app.router.add_post("/v1/sessions/{session_id}/rollback", session_rollback)
    app.router.add_delete("/v1/sessions/{session_id}", session_delete)
    app.router.add_post("/v1/profile", profile)
    app.router.add_post("/v1/parse-custom-tool", parse_custom_tool)
    app.router.add_post("/v1/execute-custom-tool", execute_custom_tool)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/metrics", metrics_endpoint)
    app.router.add_get("/v1/traces", list_traces)
    app.router.add_get("/v1/traces/{trace_id}", get_trace)
    app.router.add_get("/v1/fleet", fleet_snapshot)
    app.router.add_get("/v1/fleet/events", fleet_events)
    app.router.add_get("/v1/slo", slo_endpoint)
    app.router.add_get("/v1/tenants", tenants_endpoint)
    app.router.add_get("/v1/autoscale", autoscale_endpoint)
    app.router.add_get("/v1/serving", serving_snapshot)
    app.router.add_get("/v1/serving/requests", serving_requests)
    app.router.add_get("/v1/accelerator", accelerator_snapshot)
    app.router.add_get("/v1/events", list_events)
    app.router.add_get("/v1/debug/bundle", debug_bundle_endpoint)
    app.router.add_get("/v1/debug/tasks", debug_tasks)
    app.router.add_get("/v1/debug/pprof", debug_pprof)
    return app
