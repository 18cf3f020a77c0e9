"""Metric-naming conventions lint (tier-1): every metric the service can
register must carry the ``bci_`` namespace prefix, non-empty HELP text, and
unit-suffixed names where the type implies a unit (counters ``_total``,
histograms ``_seconds``/``_bytes``). The registry itself must refuse a name
re-registered as a different metric type — the duplicate-registration bug
class where two components silently share one exposition block with the
wrong TYPE line."""

import pytest

from bee_code_interpreter_tpu.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
)


def build_service_registry(tmp_path) -> Registry:
    """Assemble the registry the way the composition root does — kubernetes
    backend with local fallback, both transports, admission, tracing — so
    the lint sees every metric the service can register."""
    from bee_code_interpreter_tpu.application_context import ApplicationContext
    from bee_code_interpreter_tpu.config import Config

    ctx = ApplicationContext(
        Config(
            executor_backend="kubernetes",
            fallback_to_local=True,
            file_storage_path=str(tmp_path / "objects"),
            local_workspace_root=str(tmp_path / "ws"),
            disable_dep_install=True,
            # telemetry export + SLO objectives, so their metrics register
            otlp_endpoint="http://127.0.0.1:4318",
            slo_availability=99.5,
            slo_latency_ms="2000:99",
            # fleet-wide tenancy (ISSUE 16): a lease client wires the
            # replica-side bci_quota_lease_* surface (never started here)
            tenants="alpha:weight=2:rps=5",
            quota_lease_urls="http://127.0.0.1:1",
        )
    )
    _ = ctx.code_executor  # registers executor, breaker, pool, fallback
    _ = ctx.admission
    _ = ctx.http_server
    _ = ctx.grpc_server
    return ctx.metrics


def register_serving_metrics(registry: Registry) -> None:
    """The models-layer registrations (batcher + engine), on a tiny CPU
    config — construction registers everything; no decode needed."""
    import jax

    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.models.engine import Engine
    from bee_code_interpreter_tpu.models.serving import ContinuousBatcher

    config = T.TransformerConfig.tiny()
    params = T.init_params(config, jax.random.PRNGKey(0))
    batcher = ContinuousBatcher(
        params, config, max_batch=2, n_pages=8, page_size=4,
        max_pages_per_seq=2, metrics=registry,
    )
    Engine(batcher, metrics=registry)


def register_router_metrics(registry: Registry) -> None:
    """The fleet-router edge (docs/fleet.md) registers into ITS OWN
    registry in production; constructing one here holds its bci_router_*
    family to the same conventions."""
    import asyncio

    from bee_code_interpreter_tpu.fleet import FleetRouter
    from bee_code_interpreter_tpu.tenancy import TenantRegistry, parse_tenants

    router = FleetRouter(
        [("r0", "http://127.0.0.1:1")],
        metrics=registry,
        # fleet-wide tenancy (ISSUE 16): a declared tenant table + a peer
        # edge register the quota-ledger and gossip families too
        tenancy=TenantRegistry(parse_tenants("alpha:weight=2:rps=5")),
        peers=[("p1", "http://127.0.0.1:2")],
    )
    asyncio.run(router.stop())


def register_device_metrics(registry: Registry) -> None:
    """The accelerator plane (ISSUE 20): DeviceMonitor registers the
    compile/step families at construction; the per-device
    ``bci_device_hbm_bytes`` gauge series appear with the first memory
    sample, which needs an attached batcher (the control plane alone never
    touches the device) — a stub handing in one row stands in for it."""
    from bee_code_interpreter_tpu.observability.device import DeviceMonitor

    class StubBatcher:
        mesh = None

        def set_device_monitor(self, monitor) -> None:
            pass

        def device_memory(self) -> list[dict]:
            return [
                {"device": "cpu:0", "platform": "cpu", "live_bytes": 0,
                 "peak_bytes": 0, "limit_bytes": None, "estimated": True}
            ]

        def kv_telemetry(self) -> dict:
            return {}

    DeviceMonitor(metrics=registry).attach(StubBatcher())


def register_loadgen_metrics(registry: Registry) -> None:
    """The capacity harness's client-side family (ISSUE 18): the open-loop
    generator registers its sent/lag/offered surface when handed a
    registry — same conventions as the service it measures."""
    from bee_code_interpreter_tpu.loadgen import OpenLoopGenerator

    OpenLoopGenerator(
        client=None, base_url="http://127.0.0.1:1", metrics=registry
    )


def test_every_registered_metric_follows_conventions(tmp_path):
    registry = build_service_registry(tmp_path)
    register_serving_metrics(registry)
    register_router_metrics(registry)
    register_loadgen_metrics(registry)
    register_device_metrics(registry)
    metrics = registry.metrics
    assert len(metrics) >= 20, sorted(metrics)  # the wiring actually ran

    # The fleet-observability metrics (ISSUE 3) must be part of the wired
    # surface, so this lint covers their prefix/HELP/unit conventions too:
    # silently dropping one of them from the composition root would
    # otherwise pass unnoticed.
    for required in (
        "bci_pool_spawn_seconds",
        "bci_pool_utilization",
        "bci_pod_reaped_total",
        "bci_execution_cpu_seconds",
        "bci_execution_peak_rss_bytes",
        # proactive resilience (ISSUE 4): supervisor / replay / hedge / drain
        "bci_supervisor_probe_seconds",
        "bci_execution_replays_total",
        "bci_hedge_total",
        "bci_drain_inflight",
        # telemetry export + SLOs (ISSUE 5)
        "bci_telemetry_exported_total",
        "bci_telemetry_dropped_total",
        "bci_telemetry_queue_depth",
        "bci_slo_error_budget_remaining_ratio",
        "bci_slo_burn_rate",
        # edge static analysis (ISSUE 6): the pre-flight code gate
        "bci_analysis_seconds",
        "bci_analysis_rejections_total",
        "bci_analysis_warnings_total",
        "bci_analysis_dep_predictions_total",
        # dataflow layer + cost classes (ISSUE 12): dynamic-import
        # resolution accounting and the scheduling hint, plus the
        # cost-aware heavy lane's occupancy gauge
        "bci_analysis_dynamic_imports_total",
        "bci_analysis_cost_class_total",
        "bci_admission_heavy_in_flight",
        # sessions (ISSUE 7): leased sandboxes + checkpoint/rollback
        "bci_session_active",
        "bci_session_lease_seconds",
        "bci_session_expirations_total",
        # flight recorder + loop health + continuous profiler (ISSUE 8)
        "bci_events_emitted_total",
        "bci_events_dropped_total",
        "bci_event_loop_lag_seconds",
        "bci_loop_stalls_total",
        "bci_contprof_samples_total",
        # streaming promoted from bench-only numbers to production metrics
        "bci_stream_ttfb_seconds",
        "bci_stream_chunks_total",
        # serving deep observability (ISSUE 9): the ServingMonitor's
        # per-request rollups register in the composition root; the
        # batcher/engine aggregates register at model wiring (the
        # register_serving_metrics call above)
        "bci_serving_requests_total",
        "bci_serving_request_seconds",
        "bci_serving_preemptions_total",
        "bci_serving_spec_tokens_total",
        "bci_serving_spec_accept_ratio",
        "bci_serving_prefix_hit_ratio",
        "bci_serving_page_fragmentation",
        "bci_serving_ttft_seconds",
        "bci_serving_inter_token_seconds",
        "bci_serving_step_seconds",
        "bci_serving_tokens_total",
        "bci_serving_active_rows",
        "bci_serving_batch_occupancy",
        "bci_serving_free_pages",
        "bci_serving_tokens_per_second",
        "bci_serving_queue_wait_seconds",
        "bci_serving_requeues_total",
        "bci_serving_queue_rejected_total",
        "bci_serving_queue_depth",
        # capacity observability + predictive autoscaling (ISSUE 10): the
        # demand tracker + forecaster register in the composition root, the
        # autoscaler with the pool executor
        "bci_demand_rps",
        "bci_forecast_rps",
        "bci_warm_pop_ratio",
        "bci_pool_target_size",
        "bci_autoscale_decisions_total",
        # multi-tenant isolation (ISSUE 13): per-tenant admission/quota/
        # usage surface + the label-cardinality guard's overflow counter
        "bci_tenant_shed_total",
        "bci_tenant_admitted_total",
        "bci_tenant_queue_wait_seconds",
        "bci_tenant_in_flight",
        "bci_tenant_queue_depth",
        "bci_tenant_requests_total",
        "bci_tenant_cpu_seconds_total",
        "bci_tenant_bytes_total",
        "bci_metrics_label_overflow_total",
        # fleet router (ISSUE 11): the replica-aware edge's own surface
        "bci_router_requests_total",
        "bci_router_request_seconds",
        "bci_router_retries_total",
        "bci_router_affinity_total",
        "bci_router_lease_migrations_total",
        "bci_router_replicas",
        "bci_router_pinned_sessions",
        # fleet-wide tenancy (ISSUE 16): router-held quota-lease ledger,
        # peer gossip, tenant retry budgets, and the replica-side lease
        # client's refresh/fleet-size surface
        "bci_router_quota_leases_total",
        "bci_router_quota_active_leases",
        "bci_router_peer_sync_total",
        "bci_router_peer_up",
        "bci_router_retry_budget_denied_total",
        "bci_quota_lease_refresh_total",
        "bci_quota_lease_fleet_size",
        # fleet observability plane (ISSUE 17): federated scatter-gather
        # at the router edge + the router's own stage-span histogram
        # (bci_stage_seconds registers via the router Tracer; slo gauges
        # via the router SloEngine when objectives are configured)
        "bci_federation_requests_total",
        "bci_federation_replica_errors_total",
        "bci_federation_fanout_seconds",
        "bci_stage_seconds",
        # capacity harness + forecaster→replica-count loop (ISSUE 18):
        # the open-loop generator's client-side family and the federated
        # recommendation gauge the router edge publishes
        "bci_loadgen_sent_total",
        "bci_loadgen_lag_seconds",
        "bci_loadgen_offered_rps",
        "bci_fleet_target_replicas",
        # accelerator observability plane (ISSUE 20): compile/retrace
        # tracking, per-device HBM accounting, and mesh-shaped step
        # telemetry from the DeviceMonitor
        "bci_compile_total",
        "bci_compile_seconds",
        "bci_device_hbm_bytes",
        "bci_device_step_seconds",
    ):
        assert required in metrics, f"{required}: not registered by the wiring"
    assert isinstance(metrics["bci_pool_spawn_seconds"], Histogram)
    assert isinstance(metrics["bci_pool_utilization"], Gauge)
    assert isinstance(metrics["bci_pod_reaped_total"], Counter)
    assert isinstance(metrics["bci_execution_cpu_seconds"], Histogram)
    assert isinstance(metrics["bci_execution_peak_rss_bytes"], Histogram)
    assert isinstance(metrics["bci_supervisor_probe_seconds"], Histogram)
    assert isinstance(metrics["bci_execution_replays_total"], Counter)
    assert isinstance(metrics["bci_hedge_total"], Counter)
    assert isinstance(metrics["bci_drain_inflight"], Gauge)
    assert isinstance(metrics["bci_telemetry_exported_total"], Counter)
    assert isinstance(metrics["bci_telemetry_dropped_total"], Counter)
    assert isinstance(metrics["bci_telemetry_queue_depth"], Gauge)
    assert isinstance(metrics["bci_slo_error_budget_remaining_ratio"], Gauge)
    assert isinstance(metrics["bci_slo_burn_rate"], Gauge)
    assert isinstance(metrics["bci_analysis_seconds"], Histogram)
    assert isinstance(metrics["bci_analysis_rejections_total"], Counter)
    assert isinstance(metrics["bci_analysis_dep_predictions_total"], Counter)
    assert isinstance(metrics["bci_analysis_dynamic_imports_total"], Counter)
    assert isinstance(metrics["bci_analysis_cost_class_total"], Counter)
    assert isinstance(metrics["bci_admission_heavy_in_flight"], Gauge)
    assert isinstance(metrics["bci_session_active"], Gauge)
    assert isinstance(metrics["bci_session_lease_seconds"], Histogram)
    assert isinstance(metrics["bci_session_expirations_total"], Counter)
    assert isinstance(metrics["bci_events_emitted_total"], Counter)
    assert isinstance(metrics["bci_events_dropped_total"], Counter)
    assert isinstance(metrics["bci_event_loop_lag_seconds"], Histogram)
    assert isinstance(metrics["bci_loop_stalls_total"], Counter)
    assert isinstance(metrics["bci_contprof_samples_total"], Counter)
    assert isinstance(metrics["bci_stream_ttfb_seconds"], Histogram)
    assert isinstance(metrics["bci_stream_chunks_total"], Counter)
    assert isinstance(metrics["bci_serving_requests_total"], Counter)
    assert isinstance(metrics["bci_serving_request_seconds"], Histogram)
    assert isinstance(metrics["bci_serving_preemptions_total"], Counter)
    assert isinstance(metrics["bci_serving_spec_tokens_total"], Counter)
    assert isinstance(metrics["bci_serving_spec_accept_ratio"], Gauge)
    assert isinstance(metrics["bci_serving_prefix_hit_ratio"], Gauge)
    assert isinstance(metrics["bci_serving_page_fragmentation"], Gauge)
    assert isinstance(metrics["bci_demand_rps"], Gauge)
    assert isinstance(metrics["bci_forecast_rps"], Gauge)
    assert isinstance(metrics["bci_warm_pop_ratio"], Gauge)
    assert isinstance(metrics["bci_pool_target_size"], Gauge)
    assert isinstance(metrics["bci_autoscale_decisions_total"], Counter)
    assert isinstance(metrics["bci_tenant_shed_total"], Counter)
    assert isinstance(metrics["bci_tenant_queue_wait_seconds"], Histogram)
    assert isinstance(metrics["bci_tenant_in_flight"], Gauge)
    assert isinstance(metrics["bci_tenant_requests_total"], Counter)
    assert isinstance(metrics["bci_tenant_cpu_seconds_total"], Counter)
    assert isinstance(metrics["bci_metrics_label_overflow_total"], Counter)
    assert isinstance(metrics["bci_router_requests_total"], Counter)
    assert isinstance(metrics["bci_router_request_seconds"], Histogram)
    assert isinstance(metrics["bci_router_lease_migrations_total"], Counter)
    assert isinstance(metrics["bci_router_replicas"], Gauge)
    assert isinstance(metrics["bci_router_quota_leases_total"], Counter)
    assert isinstance(metrics["bci_router_quota_active_leases"], Gauge)
    assert isinstance(metrics["bci_router_peer_sync_total"], Counter)
    assert isinstance(metrics["bci_router_peer_up"], Gauge)
    assert isinstance(
        metrics["bci_router_retry_budget_denied_total"], Counter
    )
    assert isinstance(metrics["bci_quota_lease_refresh_total"], Counter)
    assert isinstance(metrics["bci_quota_lease_fleet_size"], Gauge)
    assert isinstance(metrics["bci_loadgen_sent_total"], Counter)
    assert isinstance(metrics["bci_loadgen_lag_seconds"], Histogram)
    assert isinstance(metrics["bci_loadgen_offered_rps"], Gauge)
    assert isinstance(metrics["bci_fleet_target_replicas"], Gauge)
    assert isinstance(metrics["bci_compile_total"], Counter)
    assert isinstance(metrics["bci_compile_seconds"], Histogram)
    assert isinstance(metrics["bci_device_hbm_bytes"], Gauge)
    assert isinstance(metrics["bci_device_step_seconds"], Histogram)

    for name, metric in metrics.items():
        assert name.startswith("bci_"), (
            f"{name}: metrics must live in the bci_ namespace"
        )
        assert metric.help and metric.help.strip(), (
            f"{name}: HELP text must be non-empty"
        )
        if isinstance(metric, Counter):
            assert name.endswith("_total"), (
                f"{name}: counters must end in _total"
            )
        elif isinstance(metric, Histogram):
            assert name.endswith(("_seconds", "_bytes")), (
                f"{name}: histograms must be unit-suffixed "
                "(_seconds or _bytes)"
            )
        else:
            assert isinstance(metric, Gauge), f"{name}: unknown metric type"
            # gauges describe states/counts; they must not masquerade as
            # counters or timers
            assert not name.endswith(("_total", "_seconds")), (
                f"{name}: gauge misusing a counter/histogram unit suffix"
            )

    # the full exposition renders without error and every metric appears once
    text = registry.expose()
    for name in metrics:
        assert text.count(f"# HELP {name} ") == 1, (
            f"{name}: duplicate or missing exposition block"
        )


def test_every_serving_metric_is_documented(tmp_path):
    """asynclint's undocumented-metric rule scopes to the control plane
    (api/ + services/ + resilience/ + observability/ + sessions/) and
    deliberately does not lint models/ — hold the serving-engine metrics
    to the same standard here: every registered ``bci_serving_*`` name
    must appear (word-bounded) in docs/observability.md."""
    import re
    from pathlib import Path

    registry = build_service_registry(tmp_path)
    register_serving_metrics(registry)
    doc = (
        Path(__file__).resolve().parent.parent / "docs" / "observability.md"
    ).read_text()
    serving = sorted(
        n for n in registry.metrics if n.startswith("bci_serving_")
    )
    assert len(serving) >= 16, serving  # both layers actually registered
    for name in serving:
        assert re.search(rf"\b{name}\b", doc), (
            f"{name}: registered but not documented in docs/observability.md"
        )


def test_every_cost_class_label_is_documented():
    """`bci_analysis_cost_class_total{class}` is a CLOSED label set
    (COST_CLASSES); an operator reading docs/observability.md must find
    every value it can take — `accelerator` joined the set with the
    jaxlint PR and must not be the last one anyone documents."""
    from pathlib import Path

    from bee_code_interpreter_tpu.analysis import COST_CLASSES

    doc = (
        Path(__file__).resolve().parent.parent / "docs" / "observability.md"
    ).read_text()
    row = next(
        line
        for line in doc.splitlines()
        if "bci_analysis_cost_class_total" in line and line.startswith("|")
    )
    for cls in COST_CLASSES:
        assert f"`{cls}`" in row, (
            f"cost class {cls!r} missing from the "
            "bci_analysis_cost_class_total row in docs/observability.md"
        )
    assert "accelerator" in row


def test_analysis_stage_appears_in_stage_seconds(tmp_path):
    """The edge gate's work is a first-class request stage: one analyzed
    submission under a trace must surface as
    ``bci_stage_seconds{stage="analysis"}`` — the same histogram every
    other stage (admission/spawn/upload/execute/download) feeds, so
    dashboards see the gate's cost next to what it saves."""
    from bee_code_interpreter_tpu.analysis import WorkloadAnalyzer
    from bee_code_interpreter_tpu.observability import Tracer

    registry = build_service_registry(tmp_path)
    tracer = Tracer(metrics=registry)
    analyzer = WorkloadAnalyzer(metrics=registry)
    with tracer.trace("/v1/execute"):
        analyzer.analyze("print(1)\n")
    text = registry.expose()
    assert 'bci_stage_seconds_count{stage="analysis"} 1' in text


def test_every_seconds_histogram_carries_exemplars_when_trace_active(tmp_path):
    """Exemplar lint: an observation made under an active trace must surface
    that trace's id on the OpenMetrics exposition of EVERY ``bci_*_seconds``
    histogram — the metric↔trace linkage is only useful if no histogram
    silently opts out."""
    import re

    from bee_code_interpreter_tpu.observability import Tracer

    registry = build_service_registry(tmp_path)
    tracer = Tracer(metrics=registry)
    histograms = {
        name: metric
        for name, metric in registry.metrics.items()
        if isinstance(metric, Histogram) and name.endswith("_seconds")
    }
    assert len(histograms) >= 5, sorted(histograms)

    with tracer.trace("exemplar-lint") as trace:
        for metric in histograms.values():
            metric.observe(0.012)

    text = registry.expose(openmetrics=True)
    for name in histograms:
        pattern = re.compile(
            rf'^{name}_bucket{{[^}}]*}} \d+ '
            rf'# {{trace_id="{trace.trace_id}",span_id="[0-9a-f]{{16}}"}} '
            rf"[0-9.e+-]+ [0-9.]+$",
            re.M,
        )
        assert pattern.search(text), f"{name}: no exemplar on any bucket"
    assert text.rstrip().endswith("# EOF")

    # the classic Prometheus format must stay exemplar-free (its parsers
    # reject the syntax) and observations made OUTSIDE a trace add none
    classic = registry.expose()
    assert "trace_id=" not in classic
    assert "# EOF" not in classic
    fresh = Registry()
    fresh.histogram("bci_plain_seconds", "untraced").observe(0.5)
    assert "trace_id=" not in fresh.expose(openmetrics=True)


def test_tenant_label_cardinality_guard_collapses_to_other():
    """ISSUE 13 satellite: the Registry bounds per-label-value cardinality
    — a tenant-id flood collapses into one 'other' series past the bound,
    with every collapsed observation counted, so /metrics cannot OOM."""
    registry = Registry()
    registry.bound_label("tenant", 3)
    shed = registry.counter("bci_tenant_shed_total", "sheds per tenant")
    for i in range(50):
        shed.inc(tenant=f"flood-{i}", reason="tenant_quota")
    text = registry.expose()
    # exactly 3 distinct tenant series + the collapsed bucket
    assert text.count('reason="tenant_quota",tenant="flood-') == 3
    assert (
        'bci_tenant_shed_total{reason="tenant_quota",tenant="other"} 47'
        in text
    )
    assert 'bci_metrics_label_overflow_total{label="tenant"} 47' in text
    # already-seen values keep their own series (no flapping to "other")
    shed.inc(tenant="flood-0", reason="tenant_quota")
    assert (
        'bci_tenant_shed_total{reason="tenant_quota",tenant="flood-0"} 2'
        in registry.expose()
    )
    # histograms and gauges honor the same bound
    hist = registry.histogram("bci_tenant_queue_wait_seconds", "wait")
    for i in range(10):
        hist.observe(0.01, tenant=f"h-{i}")
    om = registry.expose()
    assert om.count("bci_tenant_queue_wait_seconds_count") <= 4
    gauge_values = iter(range(100))
    for i in range(10):
        registry.gauge(
            "bci_tenant_in_flight", "in flight",
            (lambda v: lambda: v)(next(gauge_values)),
            tenant=f"g-{i}",
        )
    assert registry.expose().count("bci_tenant_in_flight{tenant=") <= 4

    # every registry ships a default bound for the tenant label: even a
    # bare Registry cannot be flooded
    bare = Registry()
    c = bare.counter("bci_tenant_requests_total", "reqs")
    for i in range(100):
        c.inc(tenant=f"t-{i}")
    assert 'tenant="other"' in bare.expose()


def test_openmetrics_counter_family_drops_total_suffix():
    registry = Registry()
    registry.counter("bci_things_total", "things").inc(2)
    om = registry.expose(openmetrics=True)
    assert "# TYPE bci_things counter" in om
    assert "bci_things_total 2" in om  # the sample keeps the suffix
    classic = registry.expose()
    assert "# TYPE bci_things_total counter" in classic


def test_registry_rejects_type_conflicting_reregistration():
    registry = Registry()
    registry.counter("bci_things_total", "things")
    with pytest.raises(ValueError, match="already registered"):
        registry.histogram("bci_things_total", "things, but a histogram")
    with pytest.raises(ValueError, match="already registered"):
        registry.gauge("bci_things_total", "things, but a gauge", lambda: 0)
    # same name, same type remains a shared object, not an error
    assert registry.counter("bci_things_total", "things") is not None
