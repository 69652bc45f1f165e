"""Observability: tracer spans, metrics registry, /metrics endpoint.

The reference has no equivalent subsystem (SURVEY.md §5.1/§5.5); these
tests cover the capability the TPU build adds on top.
"""

from __future__ import annotations

import aiohttp

from hocuspocus_tpu.observability import (
    Metrics,
    MetricsRegistry,
    Tracer,
    disable_tracing,
    enable_tracing,
    get_tracer,
)

from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced


def test_tracer_records_spans_with_attributes():
    tracer = Tracer(enabled=True, max_spans=8)
    with tracer.span("outer", document="doc-a") as span:
        span.set("bytes", 42)
    spans = tracer.export()
    assert len(spans) == 1
    assert spans[0]["name"] == "outer"
    assert spans[0]["attributes"] == {"document": "doc-a", "bytes": 42}
    assert spans[0]["duration_ms"] >= 0


def test_tracer_disabled_is_noop():
    tracer = Tracer(enabled=False)
    with tracer.span("nope") as span:
        span.set("ignored", 1)
    assert len(tracer) == 0


def test_tracer_ring_buffer_bounded():
    tracer = Tracer(enabled=True, max_spans=4)
    for i in range(10):
        with tracer.span(f"s{i}"):
            pass
    spans = tracer.export()
    assert len(spans) == 4
    assert [s["name"] for s in spans] == ["s6", "s7", "s8", "s9"]


def test_span_lands_in_the_ring_with_no_profiler_capture():
    tracer = Tracer(enabled=True)
    with tracer.span("merge", slots=4) as span:
        span.set("integrated", 128)
    assert tracer.export()[0]["attributes"] == {"slots": 4, "integrated": 128}


def test_global_tracer_enable_disable():
    tracer = enable_tracing(max_spans=16)
    try:
        assert get_tracer() is tracer
        with tracer.span("x"):
            pass
        assert len(tracer) == 1
    finally:
        disable_tracing()
        tracer.clear()


def test_metrics_counter_and_gauge_exposition():
    reg = MetricsRegistry()
    c = reg.counter("demo_total", "Demo counter")
    c.inc()
    c.inc(2, kind="sync")
    g = reg.gauge("demo_current", "Demo gauge", fn=lambda: 3)
    text = reg.expose()
    assert "# TYPE demo_total counter" in text
    assert "demo_total 1" in text
    assert 'demo_total{kind="sync"} 2' in text
    assert "demo_current 3" in text
    assert g.value() == 3


def test_metrics_histogram_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "Latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.expose()
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="0.1"} 2' in text
    assert 'lat_seconds_bucket{le="1"} 3' in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text
    assert h.count == 4


async def test_metrics_extension_counts_lifecycle_and_serves_endpoint():
    metrics = Metrics()
    server = await new_hocuspocus(extensions=[metrics])
    provider = new_provider(server, name="metrics-doc")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "hello")

        await retryable_assertion(lambda: _assert_positive(metrics.changes.value()))
        assert metrics.connects.value() == 1
        assert metrics.loads.value() == 1

        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server.http_url}/metrics") as response:
                assert response.status == 200
                body = await response.text()
        assert "hocuspocus_connections 1" in body
        assert "hocuspocus_documents 1" in body
        assert "hocuspocus_connects_total 1" in body
        assert "hocuspocus_document_loads_total 1" in body
        assert "hocuspocus_document_load_seconds_count 1" in body

        # non-metrics requests still get the default response
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server.http_url}/other") as response:
                assert response.status == 200
                assert "Welcome" in await response.text()
        assert metrics.http_requests.value() == 1
    finally:
        provider.destroy()
        await server.destroy()

    assert metrics.disconnects.value() == 1
    assert metrics.unloads.value() == 1


async def test_tracing_captures_message_spans_end_to_end():
    tracer = enable_tracing(max_spans=512)
    tracer.clear()
    server = await new_hocuspocus()
    provider = new_provider(server, name="traced-doc")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "traced")

        def has_spans():
            names = {s["name"] for s in tracer.export()}
            assert "message.apply" in names, names
            assert any(n.startswith("hooks.") for n in names), names

        await retryable_assertion(has_spans)
        apply_spans = [
            s for s in tracer.export() if s["name"] == "message.apply"
        ]
        assert all(s["attributes"]["document"] == "traced-doc" for s in apply_spans)
        assert all(s["attributes"]["bytes"] > 0 for s in apply_spans)
    finally:
        disable_tracing()
        tracer.clear()
        provider.destroy()
        await server.destroy()


def _assert_positive(value: float) -> None:
    assert value > 0


async def test_metrics_exposes_tpu_plane_counters():
    """A serve-mode plane's health counters surface on /metrics."""
    import aiohttp

    from hocuspocus_tpu.observability import Metrics
    from hocuspocus_tpu.tpu import TpuMergeExtension
    from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced

    ext = TpuMergeExtension(num_docs=8, capacity=512, flush_interval_ms=1, serve=True)
    metrics = Metrics()
    server = await new_hocuspocus(extensions=[metrics, ext])
    provider = new_provider(server, name="metered")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "counted")

        def broadcasted():
            assert ext.plane.counters["plane_broadcasts"] >= 1

        await retryable_assertion(broadcasted)
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server.http_url}/metrics") as response:
                body = await response.text()
        lines = body.splitlines()
        assert any(
            line.startswith("hocuspocus_tpu_plane_broadcasts ") for line in lines
        )
        assert "hocuspocus_tpu_plane_docs_retired_unsupported 0" in lines
        assert "hocuspocus_tpu_plane_arena_rows_in_use 1" in lines
        assert any(
            line.startswith("hocuspocus_tpu_plane_ops_integrated ") for line in lines
        )
    finally:
        provider.destroy()
        await server.destroy()


async def test_supervisor_metrics_visible_in_prometheus_exposition():
    """Plane supervisor surface (tpu/supervisor.py): state, breaker
    transitions and canary latency must land in the /metrics text so a
    balancer/alerting stack can watch plane health (ISSUE acceptance)."""
    from hocuspocus_tpu.tpu import SupervisedTpuMergeExtension

    metrics = Metrics()
    ext = SupervisedTpuMergeExtension(
        serve=True,
        num_docs=8,
        capacity=256,
        flush_interval_ms=1,
        init_timeout=60.0,
        watchdog_interval=0.05,
        canary_deadline=1.0,
    )
    server = await new_hocuspocus(extensions=[metrics, ext])
    provider = new_provider(server, name="sup-metrics")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "observe me")
        # READY + at least one SUCCESSFUL canary probe (latency recorded)
        await retryable_assertion(
            lambda: _assert_positive(
                (ext.supervisor.state == "ready")
                and (ext.supervisor.last_canary_latency is not None)
            )
        )

        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server.http_url}/metrics") as response:
                assert response.status == 200
                body = await response.text()

        # supervisor state gauge: 1 == ready
        assert "hocuspocus_tpu_supervisor_state 1" in body
        assert "hocuspocus_tpu_supervisor_breaker_state 0" in body
        # the boot transition was recorded with exact labels
        assert (
            'hocuspocus_tpu_supervisor_transitions_total{from_state="initializing",to_state="ready"} 1'
            in body
        )
        # breaker transition counter is present (zero so far)
        assert "hocuspocus_tpu_supervisor_breaker_transitions_total" in body
        # canary latency: histogram observed at least once + last-value gauge
        count_line = next(
            line
            for line in body.splitlines()
            if line.startswith("hocuspocus_tpu_supervisor_canary_seconds_count")
        )
        assert int(count_line.split()[-1]) >= 1
        assert "hocuspocus_tpu_supervisor_canary_latency_seconds" in body
        # the plane's own counters bound at hot-attach time
        assert "hocuspocus_tpu_plane_cpu_fallbacks" in body
        assert "hocuspocus_tpu_plane_arena_rows_in_use" in body
    finally:
        provider.destroy()
        await server.destroy()
