"""The unified metrics pipeline: registry semantics, the strict text
exposition contract against a live daemon, exemplars, and route-label
cardinality bounds."""

import json
import math
import urllib.error
import urllib.parse
import urllib.request

import pytest

from keto_tpu.x.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    MetricsRegistry,
    NullMetricsRegistry,
    normalize_route,
    parse_exposition,
)

# -- registry unit tests -------------------------------------------------------


def test_counter_gauge_histogram_render_and_parse_round_trip():
    m = MetricsRegistry()
    c = m.counter("t_requests_total", "requests", ("route", "code"))
    c.inc(("/check", "200"))
    c.inc(("/check", "200"), by=2)
    c.inc(("/check", "403"))
    g = m.gauge("t_depth", "queue depth")
    g.set((), 7)
    h = m.histogram("t_latency_seconds", "latency", ("route",), buckets=(0.1, 1.0))
    h.observe(("/check",), 0.05)
    h.observe(("/check",), 0.5)
    h.observe(("/check",), 5.0)
    families = parse_exposition(m.render())
    assert families["t_requests_total"]["type"] == "counter"
    samples = {
        tuple(sorted(l.items())): v
        for _, l, v in families["t_requests_total"]["samples"]
    }
    assert samples[(("code", "200"), ("route", "/check"))] == 3
    assert samples[(("code", "403"), ("route", "/check"))] == 1
    assert families["t_depth"]["samples"] == [("t_depth", {}, 7.0)]
    hist = {
        (name, l.get("le")): v
        for name, l, v in families["t_latency_seconds"]["samples"]
    }
    assert hist[("t_latency_seconds_bucket", "0.1")] == 1
    assert hist[("t_latency_seconds_bucket", "1")] == 2
    assert hist[("t_latency_seconds_bucket", "+Inf")] == 3
    assert hist[("t_latency_seconds_count", None)] == 3
    assert hist[("t_latency_seconds_sum", None)] == pytest.approx(5.55)


def test_counter_must_end_in_total_and_shapes_are_stable():
    m = MetricsRegistry()
    with pytest.raises(ValueError, match="_total"):
        m.counter("t_requests", "bad name")
    c = m.counter("t_x_total", "x", ("a",))
    assert m.counter("t_x_total", "x", ("a",)) is c  # idempotent
    with pytest.raises(ValueError, match="different shape"):
        m.counter("t_x_total", "x", ("a", "b"))
    with pytest.raises(ValueError, match="ascending"):
        m.histogram("t_h_seconds", "h", buckets=(1.0, 0.5))


def test_label_escaping_survives_render_and_parse():
    m = MetricsRegistry()
    c = m.counter("t_esc_total", "escaping", ("v",))
    nasty = 'quote " backslash \\ newline \n end'
    c.inc((nasty,))
    text = m.render()
    families = parse_exposition(text)
    (_, labels, value) = families["t_esc_total"]["samples"][0]
    assert value == 1
    # the parsed (still-escaped) form decodes back to the original
    decoded = labels["v"].replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")
    assert decoded == nasty


def test_callback_families_read_live_values():
    m = MetricsRegistry()
    state = {"n": 0}
    m.register_callback(
        "t_live_total", "counter", "live", lambda: [((), float(state["n"]))]
    )
    assert "t_live_total 0" in m.render()
    state["n"] = 41
    assert "t_live_total 41" in m.render()


def test_broken_callback_never_breaks_the_scrape():
    m = MetricsRegistry()

    def boom():
        raise RuntimeError("stat source died")

    m.register_callback("t_broken_total", "counter", "broken", boom)
    m.counter("t_ok_total", "fine").inc(())
    families = parse_exposition(m.render())
    assert families["t_ok_total"]["samples"][0][2] == 1
    assert families["t_broken_total"]["samples"] == []


def test_null_registry_is_inert():
    m = NullMetricsRegistry()
    m.counter("x_total", "x").inc(())
    m.histogram("h_seconds", "h").observe((), 1.0, trace_id="t")
    m.gauge("g", "g").set((), 5)
    assert m.render() == ""
    assert not m.enabled


def test_exemplar_keeps_slowest_sample_and_lands_in_its_bucket():
    m = MetricsRegistry()
    h = m.histogram("t_lat_seconds", "lat", buckets=(0.1, 1.0, 10.0))
    h.observe((), 0.05, trace_id="fast")
    h.observe((), 3.0, trace_id="slowest")
    h.observe((), 0.5, trace_id="mid")
    text = m.render(openmetrics=True)
    ex_lines = [l for l in text.splitlines() if " # {" in l]
    assert len(ex_lines) == 1, text
    assert 'le="10"' in ex_lines[0] and 'trace_id="slowest"' in ex_lines[0]
    assert text.rstrip().endswith("# EOF")
    # plain Prometheus rendering carries no exemplars
    assert " # {" not in m.render()


def test_parse_exposition_rejects_violations():
    good = "# HELP a_total ok\n# TYPE a_total counter\na_total 1\n"
    parse_exposition(good)
    with pytest.raises(ValueError, match="_total"):
        parse_exposition("# HELP a ok\n# TYPE a counter\na 1\n")
    with pytest.raises(ValueError, match="duplicate sample"):
        parse_exposition(
            "# HELP a_total ok\n# TYPE a_total counter\na_total 1\na_total 2\n"
        )
    with pytest.raises(ValueError, match="without preceding HELP"):
        parse_exposition("# TYPE a_total counter\na_total 1\n")
    with pytest.raises(ValueError, match="not cumulative"):
        parse_exposition(
            "# HELP h ok\n# TYPE h histogram\n"
            'h_bucket{le="0.1"} 5\nh_bucket{le="+Inf"} 3\nh_sum 1\nh_count 3\n'
        )
    with pytest.raises(ValueError, match="missing [+]Inf"):
        parse_exposition(
            "# HELP h ok\n# TYPE h histogram\n"
            'h_bucket{le="0.1"} 1\nh_sum 1\nh_count 1\n'
        )


def test_normalize_route_bounds_cardinality():
    assert normalize_route("/check") == "/check"
    assert normalize_route("/relation-tuples") == "/relation-tuples"
    for path in ("/admin", "/check/../etc", "/relation-tuples/123", "/%2e%2e"):
        assert normalize_route(path) == "other"


# -- live daemon: the strict scrape contract -----------------------------------


NAMESPACES = [{"id": 0, "name": "files"}]


@pytest.fixture(scope="module")
def daemon():
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry

    cfg = Config(
        overrides={
            "namespaces": NAMESPACES,
            "dsn": "memory",
            "serve.read.port": 0,
            "serve.write.port": 0,
            "tracing.provider": "memory",
        }
    )
    d = Daemon(Registry(cfg))
    d.serve_all(block=False)
    put = {"namespace": "files", "object": "o", "relation": "r", "subject_id": "u"}
    req = urllib.request.Request(
        f"http://127.0.0.1:{d.write_port}/relation-tuples",
        data=json.dumps(put).encode(), method="PUT",
        headers={"Content-Type": "application/json", "X-Idempotency-Key": "m-1"},
    )
    urllib.request.urlopen(req)
    urllib.request.urlopen(req)  # idempotent replay → replay counter
    yield d
    d.shutdown()


def _get(port, path, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def test_live_scrape_is_strictly_valid_and_spans_the_stack(daemon):
    """Every line of a real daemon's /metrics parses under the strict
    contract, and the family set spans REST, gRPC, batcher, engine
    slices, maintenance, health, tracer, and persistence."""
    import grpc
    from ory.keto.acl.v1alpha1 import check_service_pb2

    # REST traffic: an allow, a deny, a health probe (excluded)
    assert _get(daemon.read_port, "/check?namespace=files&object=o&relation=r&subject_id=u")[0] == 200
    assert _get(daemon.read_port, "/check?namespace=files&object=o&relation=r&subject_id=x")[0] == 403
    assert _get(daemon.read_port, "/health/ready")[0] == 200
    # gRPC traffic
    channel = grpc.insecure_channel(f"127.0.0.1:{daemon.read_port}")
    stub = channel.unary_unary(
        "/ory.keto.acl.v1alpha1.CheckService/Check",
        request_serializer=check_service_pb2.CheckRequest.SerializeToString,
        response_deserializer=check_service_pb2.CheckResponse.FromString,
    )
    assert stub(
        check_service_pb2.CheckRequest(
            namespace="files", object="o", relation="r", subject={"id": "u"}
        ),
        timeout=10,
    ).allowed
    channel.close()

    status, text, headers = _get(daemon.read_port, "/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    families = parse_exposition(text)  # raises on ANY violation
    assert len(families) >= 12
    for required in (
        "keto_http_requests_total",
        "keto_http_request_duration_seconds",
        "keto_grpc_requests_total",
        "keto_grpc_request_duration_seconds",
        "keto_check_queue_depth",
        "keto_check_shed_total",
        "keto_check_deadline_drops_total",
        "keto_engine_stream_slice_duration_seconds",
        "keto_maintenance_events_total",
        "keto_overlay_edges",
        "keto_health_state",
        "keto_health_transitions_total",
        "keto_tracer_spans_exported_total",
        "keto_idempotent_replays_total",
        "keto_build_info",
    ):
        assert required in families, f"{required} missing from the scrape"

    def value(family, **labels):
        for _, l, v in families[family]["samples"]:
            if all(l.get(k) == v2 for k, v2 in labels.items()):
                return v
        return None

    assert value("keto_http_requests_total", role="read", route="/check", code="200") >= 1
    assert value("keto_http_requests_total", role="read", route="/check", code="403") >= 1
    assert value("keto_http_requests_total", role="write", route="/relation-tuples", code="201") >= 2
    assert value("keto_grpc_requests_total", method="CheckService/Check", code="OK") >= 1
    assert value("keto_idempotent_replays_total") >= 1
    assert value("keto_health_state", state="serving") == 1
    assert value("keto_tracer_spans_exported_total") >= 1
    # health endpoints are excluded from request metrics
    for _, labels, _ in families["keto_http_requests_total"]["samples"]:
        assert not labels["route"].startswith("/health/")
    # both ports serve the exposition
    assert _get(daemon.write_port, "/metrics")[0] == 200


def test_pack_row_and_ell_families_are_declared_with_their_label_values(daemon):
    """What ``target_rows_mean.bulk`` and the ELL's padding share read: both
    sides of ``keto_check_pack_rows_total``, ``keto_check_packed_total``,
    both kinds of ``keto_snapshot_ell_slots`` and the widest in-degree are
    on a scrape from boot, at 0 until a chunk is packed or a graph with
    interior rows is uploaded."""
    assert _get(daemon.read_port, "/check?namespace=files&object=o&relation=r&subject_id=u")[0] == 200
    families = parse_exposition(_get(daemon.read_port, "/metrics")[1])
    assert families["keto_check_pack_rows_total"]["type"] == "counter"
    sides = {l["side"] for _, l, _ in families["keto_check_pack_rows_total"]["samples"]}
    assert sides == {"seed", "target"}
    assert families["keto_check_packed_total"]["type"] == "counter"
    assert value_of(families, "keto_check_packed_total") >= 0
    assert families["keto_snapshot_ell_slots"]["type"] == "gauge"
    kinds = {l["kind"] for _, l, _ in families["keto_snapshot_ell_slots"]["samples"]}
    assert kinds == {"edge", "pad"}
    assert value_of(families, "keto_snapshot_max_in_degree") == 0  # one direct tuple: no interior row
    assert families["keto_check_pull_words_total"]["type"] == "counter"
    assert value_of(families, "keto_check_pull_words_total") == 0  # and nothing pulled


def test_route_label_cardinality_is_bounded(daemon):
    """A path-scanning client cannot grow the route label set: 40 junk
    paths all fold into 'other' in the metrics AND the telemetry sink."""
    telemetry = daemon.registry.telemetry()
    telemetry.enabled = True  # exercise the sink's own cap too
    for i in range(40):
        status, _, _ = _get(daemon.read_port, f"/scan-{i}/../../etc/passwd-{i}")
        assert status == 404
    _, text, _ = _get(daemon.read_port, "/metrics")
    families = parse_exposition(text)
    routes = {
        l["route"] for _, l, _ in families["keto_http_requests_total"]["samples"]
    }
    from keto_tpu.x.metrics import KNOWN_ROUTES

    assert routes <= (KNOWN_ROUTES | {"other"})
    assert value_of(families, "keto_http_requests_total", route="other", code="404") >= 40
    telemetry_routes = [r for r in telemetry.snapshot() if "scan" in r]
    assert telemetry_routes == [], "telemetry recorded unbounded route labels"


def value_of(families, family, **labels):
    for _, l, v in families[family]["samples"]:
        if all(l.get(k) == v2 for k, v2 in labels.items()):
            return v
    return None


def test_openmetrics_exemplar_links_to_a_real_trace(daemon):
    """The slowest /check sample's exemplar carries a trace id that the
    memory tracer actually finished a span for."""
    _get(daemon.read_port, "/check?namespace=files&object=o&relation=r&subject_id=u")
    status, text, headers = _get(
        daemon.read_port, "/metrics",
        headers={"Accept": "application/openmetrics-text"},
    )
    assert status == 200
    assert headers["Content-Type"].startswith("application/openmetrics-text")
    assert text.rstrip().endswith("# EOF")
    ex_lines = [
        l for l in text.splitlines()
        if l.startswith("keto_http_request_duration_seconds_bucket")
        and 'route="/check"' in l and " # {" in l
    ]
    assert ex_lines, "no exemplar on the /check latency histogram"
    import re

    trace_id = re.search(r'trace_id="([0-9a-f]{32})"', ex_lines[0]).group(1)
    finished = {s.trace_id for s in daemon.registry.tracer().finished}
    assert trace_id in finished


def test_metrics_disabled_serves_404_and_checks_still_work():
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry

    cfg = Config(
        overrides={
            "namespaces": NAMESPACES,
            "dsn": "memory",
            "serve.read.port": 0,
            "serve.write.port": 0,
            "metrics.enabled": False,
        }
    )
    d = Daemon(Registry(cfg))
    d.serve_all(block=False)
    try:
        status, body, _ = _get(d.read_port, "/metrics")
        assert status == 404
        assert "metrics disabled" in body
        status, _, headers = _get(
            d.read_port, "/check?namespace=files&object=o&relation=r&subject_id=u"
        )
        assert status == 403  # nothing written; deny — but served fine
        assert headers.get("X-Request-Id")  # correlation works without metrics
    finally:
        d.shutdown()


def test_lint_passes_on_live_scrape_and_catches_undocumented(daemon, tmp_path):
    """The CI lint logic: the live scrape passes against the documented
    table, and an undocumented family is caught."""
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "metrics_lint",
        Path(__file__).resolve().parents[1] / "scripts" / "metrics_lint.py",
    )
    lint_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint_mod)

    _, text, _ = _get(daemon.read_port, "/metrics")
    assert lint_mod.lint(text) == []
    rogue = text + "# HELP keto_rogue_total undocumented\n# TYPE keto_rogue_total counter\nketo_rogue_total 1\n"
    problems = lint_mod.lint(rogue)
    assert any("keto_rogue_total" in p and "missing from the table" in p for p in problems)
