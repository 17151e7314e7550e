"""zoowatch federation plane (ISSUE 17): time-series windows, SLO
burn-rate engine, cross-host scraping, federated scaling signals, the
supervisor's heartbeat SLO, flight-dump merging, and the metrics-docs
drift gate — plus the two acceptance runs at the bottom (heavy e2e: a
process-mode fleet behind a scraper, and a chaos run's merged flight
dumps)."""

import json
import math
import os
import re
import socket
import sys
import threading
import time
import urllib.request

import pytest

from analytics_zoo_tpu.metrics import MetricsRegistry
from analytics_zoo_tpu.metrics.merge import (
    TelemetryAggregator,
    registry_samples,
)
from analytics_zoo_tpu.metrics.slo import (
    SloEngine,
    SloSpec,
    alertz_doc,
    default_slos,
)
from analytics_zoo_tpu.metrics.timeseries import (
    TimeSeriesStore,
    fraction_le,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tools():
    tools = os.path.join(REPO, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)


def _counter_sample(name, value, labels=None):
    s = {"name": name, "kind": "counter", "value": float(value)}
    if labels:
        s["labels"] = labels
    return s


def _gauge_sample(name, value, labels=None):
    s = {"name": name, "kind": "gauge", "value": float(value)}
    if labels:
        s["labels"] = labels
    return s


def _hist_samples(name, observations, buckets=(0.1, 0.5, 1.0)):
    """Mergeable-format histogram sample via a REAL registry — the
    exact shape the scraper pulls off /telemetryz."""
    reg = MetricsRegistry()
    h = reg.histogram(name, "", buckets=buckets)
    for v in observations:
        h.observe(v)
    return [s for s in registry_samples(reg) if s["name"] == name]


# ---------------------------------------------------------------------------
# TimeSeriesStore
# ---------------------------------------------------------------------------


class TestTimeSeriesStore:
    def test_capacity_needs_two_edges(self):
        with pytest.raises(ValueError, match="capacity"):
            TimeSeriesStore(capacity=1)

    def test_counter_rate(self):
        st = TimeSeriesStore()
        st.ingest([_counter_sample("zoo_x_total", 0)], ts=100.0)
        st.ingest([_counter_sample("zoo_x_total", 50)], ts=110.0)
        assert st.rate("zoo_x_total", 20.0, now=110.0) == \
            pytest.approx(5.0)
        # single point in window: no rate
        assert st.rate("zoo_x_total", 1.0, now=110.0) == 0.0

    def test_counter_reset_degrades_not_negative(self):
        st = TimeSeriesStore()
        st.ingest([_counter_sample("zoo_x_total", 50)], ts=100.0)
        st.ingest([_counter_sample("zoo_x_total", 10)], ts=110.0)
        # reset mid-window: increase becomes the newest value, never <0
        assert st.rate("zoo_x_total", 20.0, now=110.0) == \
            pytest.approx(1.0)

    def test_rate_aggregates_across_hosts(self):
        st = TimeSeriesStore()
        for host in ("h1", "h2"):
            st.ingest([_counter_sample("zoo_x_total", 0)], ts=0.0,
                      source={"host": host})
            st.ingest([_counter_sample("zoo_x_total", 10)], ts=10.0,
                      source={"host": host})
        assert st.rate("zoo_x_total", 20.0, now=10.0) == \
            pytest.approx(2.0)
        # exact-label query selects one series
        assert st.rate("zoo_x_total", 20.0, labels={"host": "h1"},
                       now=10.0) == pytest.approx(1.0)

    def test_window_summary_sees_only_window(self):
        st = TimeSeriesStore()
        st.ingest(_hist_samples("zoo_h", [0.05] * 100), ts=100.0)
        st.ingest(_hist_samples("zoo_h", [0.05] * 100 + [0.9] * 10),
                  ts=110.0)
        summ = st.window_summary("zoo_h", 15.0, now=110.0)
        assert summ["count"] == 10  # the delta, not the lifetime 110
        assert 0.5 < summ["p50"] <= 1.0
        # empty window -> zero summary, no crash
        assert st.window_summary("zoo_h", 15.0, now=500.0)["count"] == 0

    def test_window_summary_merges_hosts_bucketwise(self):
        st = TimeSeriesStore()
        for host in ("h1", "h2"):
            st.ingest(_hist_samples("zoo_h", [0.05]), ts=100.0,
                      source={"host": host})
            st.ingest(_hist_samples("zoo_h", [0.05, 0.9, 0.9]),
                      ts=110.0, source={"host": host})
        summ = st.window_summary("zoo_h", 15.0, now=110.0)
        assert summ["count"] == 4  # (3-1) per host, summed

    def test_percentile_over_supported_quantiles_only(self):
        st = TimeSeriesStore()
        st.ingest(_hist_samples("zoo_h", [0.05]), ts=0.0)
        st.ingest(_hist_samples("zoo_h", [0.05, 0.05]), ts=1.0)
        assert st.percentile_over("zoo_h", 0.99, 10.0, now=1.0) <= 0.1
        with pytest.raises(ValueError, match="percentile_over"):
            st.percentile_over("zoo_h", 0.9, 10.0, now=1.0)

    def test_bad_fraction_gauge_points(self):
        st = TimeSeriesStore()
        st.observe("zoo_age", 1.0, ts=100.0)
        st.observe("zoo_age", 20.0, ts=101.0)
        bad, n = st.bad_fraction("zoo_age", 10.0, 5.0, now=101.0)
        assert n == 2 and bad == pytest.approx(0.5)

    def test_bad_fraction_histogram(self):
        st = TimeSeriesStore()
        st.ingest(_hist_samples("zoo_h", [0.05]), ts=100.0)
        st.ingest(_hist_samples("zoo_h", [0.05] * 10 + [0.9]),
                  ts=110.0)
        bad, n = st.bad_fraction("zoo_h", 0.5, 15.0, now=110.0)
        assert n == 10 and bad == pytest.approx(0.1, abs=1e-6)

    def test_burn_rate_semantics(self):
        st = TimeSeriesStore()
        with pytest.raises(ValueError, match="objective"):
            st.burn_rate("zoo_age", 1.0, 1.5, 10.0)
        # no data is not a violation
        assert st.burn_rate("zoo_age", 1.0, 0.9, 10.0, now=0.0) == 0.0
        st.observe("zoo_age", 5.0, ts=100.0)  # 100% bad, budget 10%
        assert st.burn_rate("zoo_age", 1.0, 0.9, 10.0, now=100.0) == \
            pytest.approx(10.0)

    def test_max_series_bound_counts_drops(self):
        st = TimeSeriesStore(max_series=1)
        st.ingest([_gauge_sample("zoo_a", 1), _gauge_sample("zoo_b", 1)],
                  ts=0.0)
        assert len(st.series()) == 1
        assert st.dropped_series == 1

    def test_ring_capacity_bounds_points(self):
        st = TimeSeriesStore(capacity=4)
        for i in range(10):
            st.observe("zoo_g", float(i), ts=float(i))
        assert next(iter(st.series().values()))["points"] == 4


class TestFractionLe:
    def test_empty_window_is_all_good(self):
        assert fraction_le((1.0,), [0, 0], 0.5) == 1.0

    def test_interpolates_inside_bucket(self):
        # 10 observations uniform in (0, 1]; threshold mid-bucket
        assert fraction_le((1.0,), [10, 0], 0.5) == pytest.approx(0.5)

    def test_threshold_above_all_bounds(self):
        assert fraction_le((1.0,), [5, 0], 2.0) == 1.0


# ---------------------------------------------------------------------------
# SloSpec / SloEngine
# ---------------------------------------------------------------------------


class TestSloSpec:
    def test_validation(self):
        ok = dict(name="s", family="f", threshold=1.0)
        SloSpec(**ok)
        with pytest.raises(ValueError, match="objective"):
            SloSpec(**dict(ok, objective=1.0))
        with pytest.raises(ValueError, match="threshold"):
            SloSpec(**dict(ok, threshold=0.0))
        with pytest.raises(ValueError, match="short_window"):
            SloSpec(**dict(ok, short_window=60.0, long_window=30.0))
        with pytest.raises(ValueError, match="kind"):
            SloSpec(**dict(ok, kind="gauge"))
        with pytest.raises(ValueError, match="burn_threshold"):
            SloSpec(**dict(ok, burn_threshold=0.0))

    def test_default_slos_cover_the_stock_planes(self):
        specs = {s.name: s for s in default_slos()}
        assert set(specs) == {"predict_latency", "step_time",
                              "checkpoint_stall", "worker_heartbeat"}
        # host liveness rides the scraper's own staleness gauge
        hb = specs["worker_heartbeat"]
        assert hb.family == "zoo_scrape_staleness_seconds"
        assert hb.kind == "ceiling"


class _FakeFlight:
    def __init__(self):
        self.events = []

    def record(self, kind, **fields):
        self.events.append(dict(kind=kind, **fields))


class TestSloEngine:
    def _spec(self):
        return SloSpec("age", "zoo_age", threshold=1.0, objective=0.9,
                       kind="ceiling", short_window=10.0,
                       long_window=20.0, burn_threshold=1.0)

    def test_fire_and_resolve_transitions(self):
        st = TimeSeriesStore()
        reg = MetricsRegistry()
        fl = _FakeFlight()
        eng = SloEngine(st, [self._spec()], registry=reg, flight=fl)
        for ts in (990.0, 995.0, 1000.0):
            st.observe("zoo_age", 5.0, ts=ts)  # all above threshold
        firing = eng.evaluate(now=1000.0)
        assert len(firing) == 1
        a = firing[0]
        assert a["slo"] == "age" and a["firing"]
        assert a["short_burn"] >= 1.0 and a["long_burn"] >= 1.0
        assert a["since"] == 1000.0
        # burn gauges + alert counter landed in the registry
        txt = {s["name"]: s for s in registry_samples(reg)
               if s.get("labels", {}).get("slo") == "age"}
        assert "zoo_slo_burn_rate" in txt
        assert txt["zoo_slo_alert_active"]["value"] == 1.0
        # "since" survives continued firing
        assert eng.evaluate(now=1001.0)[0]["since"] == 1000.0
        # an empty window resolves the alert
        assert eng.evaluate(now=2000.0) == []
        states = [d["state"] for d in eng.decision_log()]
        assert states == ["firing", "resolved"]
        assert [e["state"] for e in fl.events
                if e["kind"] == "slo_alert"] == ["firing", "resolved"]

    def test_alertz_doc_rolls_up_live_engines(self):
        st = TimeSeriesStore()
        eng = SloEngine(st, [self._spec()])
        st.observe("zoo_age", 5.0, ts=100.0)
        eng.evaluate(now=100.0)
        doc = alertz_doc()
        assert doc["engines"] >= 1
        assert any(a["slo"] == "age" and a["firing"]
                   for a in doc["firing"])

    def test_to_doc_shape(self):
        eng = SloEngine(TimeSeriesStore(), [self._spec()])
        eng.evaluate(now=0.0)
        doc = eng.to_doc()
        assert {s["name"] for s in doc["specs"]} == {"age"}
        assert doc["alerts"][0]["firing"] is False
        assert doc["decisions"] == []


# ---------------------------------------------------------------------------
# TelemetryAggregator staleness
# ---------------------------------------------------------------------------


class TestAggregatorStaleness:
    def test_stale_flagging_and_label(self):
        agg = TelemetryAggregator(stale_after=0.05)
        agg.ingest({"ts": time.time(),
                    "samples": [_counter_sample("zoo_c_total", 3)]},
                   host="h1")
        src = agg.sources()
        key = next(iter(src))
        assert src[key]["stale"] is False
        assert src[key]["age_seconds"] >= 0.0
        time.sleep(0.08)
        assert agg.sources()[key]["stale"] is True
        assert agg.stale_sources() == [key]
        labeled = [s for s in agg.labeled_samples()
                   if s["name"] == "zoo_c_total"]
        assert labeled and all(
            s["labels"].get("stale") == "true" for s in labeled)


# ---------------------------------------------------------------------------
# VarzScraper
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class TestScraperTargets:
    def test_normalize_target(self):
        from analytics_zoo_tpu.metrics.scrape import normalize_target

        assert normalize_target("127.0.0.1:9090") == \
            ("127.0.0.1:9090", "http://127.0.0.1:9090")
        assert normalize_target("http://h:1/varz") == \
            ("h:1", "http://h:1")
        assert normalize_target(("r1", "http://h:2/")) == \
            ("r1", "http://h:2")

    def test_targets_from_env(self):
        from analytics_zoo_tpu.metrics.scrape import targets_from_env

        got = targets_from_env(
            {"ZOO_SCRAPE_TARGETS": "a:1, b:2 http://c:3"})
        assert [n for n, _ in got] == ["a:1", "b:2", "c:3"]
        assert targets_from_env({}) == []


class TestVarzScraper:
    def _server(self, reg):
        from analytics_zoo_tpu.metrics import MetricsServer

        return MetricsServer(port=0, host="127.0.0.1",
                             registry=reg).start()

    def test_scrapes_live_server_into_store_and_aggregator(self):
        from analytics_zoo_tpu.metrics.health import HealthRegistry
        from analytics_zoo_tpu.metrics.scrape import VarzScraper

        reg = MetricsRegistry()
        reg.counter("zoo_demo_total", "").inc(3)
        reg.histogram("zoo_demo_seconds", "",
                      buckets=(0.1, 1.0)).observe(0.05)
        srv = self._server(reg)
        st = TimeSeriesStore()
        agg = TelemetryAggregator()
        sc = VarzScraper(targets=[("r1", srv.url)], store=st,
                         aggregator=agg, interval=0.1,
                         health=HealthRegistry())
        try:
            assert sc.poll_once() == 1
            hz = sc.healthz()
            assert hz["healthy"] is True
            assert hz["targets"]["r1"]["fetches"] == 1
            # per-host series landed, labeled by target
            assert st.label_sets("zoo_demo_total") == [{"host": "r1"}]
            # histograms survive (mergeable /telemetryz, not /varz)
            assert st.label_sets("zoo_demo_seconds")
            # the scraper's own staleness series feeds the stock SLO
            assert st.label_sets("zoo_scrape_staleness_seconds") == \
                [{"target": "r1"}]
            assert agg.sources()
        finally:
            srv.stop()

    def test_dead_target_stays_visible_and_unhealthy(self):
        from analytics_zoo_tpu.metrics.health import HealthRegistry
        from analytics_zoo_tpu.metrics.scrape import VarzScraper

        sc = VarzScraper(
            targets=[f"127.0.0.1:{_free_port()}"],
            store=TimeSeriesStore(), interval=0.1, timeout=0.5,
            health=HealthRegistry())
        assert sc.poll_once() == 0
        hz = sc.healthz()
        assert hz["healthy"] is False
        tgt = next(iter(hz["targets"].values()))
        assert tgt["errors"] == 1 and tgt["last_error"]
        assert tgt["age_seconds"] is None

    def test_empty_target_set_is_not_healthy(self):
        from analytics_zoo_tpu.metrics.health import HealthRegistry
        from analytics_zoo_tpu.metrics.scrape import VarzScraper

        sc = VarzScraper(health=HealthRegistry())
        assert sc.healthz()["healthy"] is False

    def test_discovery_merges_dynamic_targets(self):
        from analytics_zoo_tpu.metrics.health import HealthRegistry
        from analytics_zoo_tpu.metrics.scrape import VarzScraper

        reg = MetricsRegistry()
        srv = self._server(reg)
        sc = VarzScraper(store=TimeSeriesStore(), interval=0.1,
                         health=HealthRegistry(),
                         discover=lambda: {"rep-0": srv.url})
        try:
            sc.poll_once()
            assert sc.targets() == ["rep-0"]
            assert sc.healthz()["targets"]["rep-0"]["static"] is False
        finally:
            srv.stop()

    def test_varz_fallback_drops_unmergeable_histograms(self):
        import http.server

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                if self.path == "/telemetryz":  # predates the route
                    self.send_error(404)
                    return
                body = json.dumps({"ts": time.time(), "samples": [
                    _counter_sample("zoo_old_total", 2),
                    {"name": "zoo_old_seconds", "kind": "histogram",
                     "sum": 1.0, "count": 2},  # summary: unmergeable
                ]}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.end_headers()
                self.wfile.write(body)

        httpd = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        from analytics_zoo_tpu.metrics.health import HealthRegistry
        from analytics_zoo_tpu.metrics.scrape import VarzScraper

        st = TimeSeriesStore()
        sc = VarzScraper(
            targets=[f"127.0.0.1:{httpd.server_address[1]}"],
            store=st, interval=0.1, health=HealthRegistry())
        try:
            assert sc.poll_once() == 1
            assert st.label_sets("zoo_old_total")
            assert not st.label_sets("zoo_old_seconds")
        finally:
            httpd.shutdown()

    def test_fleet_discovery_reads_broker_published_urls(self):
        from analytics_zoo_tpu.metrics.scrape import (
            VARZ_KEY_PREFIX,
            fleet_varz_targets,
        )
        from analytics_zoo_tpu.serving.broker import connect_broker

        b = connect_broker("memory")
        b.hset(VARZ_KEY_PREFIX + "rep-3",
               {"url": "http://127.0.0.1:7777", "ts": time.time()})
        assert fleet_varz_targets(b)() == \
            {"rep-3": "http://127.0.0.1:7777"}


# ---------------------------------------------------------------------------
# /telemetryz + /alertz endpoints
# ---------------------------------------------------------------------------


class TestHttpEndpoints:
    def test_telemetryz_serves_mergeable_snapshot(self):
        from analytics_zoo_tpu.metrics import MetricsServer

        reg = MetricsRegistry()
        reg.histogram("zoo_h_seconds", "",
                      buckets=(0.1, 1.0)).observe(0.05)
        srv = MetricsServer(port=0, host="127.0.0.1",
                            registry=reg).start()
        try:
            doc = json.loads(urllib.request.urlopen(
                srv.url + "/telemetryz", timeout=10).read())
            hist = [s for s in doc["samples"]
                    if s["name"] == "zoo_h_seconds"]
            assert hist and hist[0]["buckets"]  # bucket vectors kept
        finally:
            srv.stop()

    def test_alertz_serves_live_engine_state(self):
        from analytics_zoo_tpu.metrics import MetricsServer

        st = TimeSeriesStore()
        eng = SloEngine(st, [SloSpec(
            "age", "zoo_age", threshold=1.0, objective=0.9,
            kind="ceiling", short_window=10.0, long_window=20.0)])
        st.observe("zoo_age", 5.0, ts=time.time())
        eng.evaluate()
        srv = MetricsServer(port=0, host="127.0.0.1",
                            registry=MetricsRegistry()).start()
        try:
            doc = json.loads(urllib.request.urlopen(
                srv.url + "/alertz", timeout=10).read())
            assert {"ts", "engines", "firing", "alerts"} <= set(doc)
            assert any(a["slo"] == "age" for a in doc["alerts"])
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# clock anchors + flight merging (the explainability satellites)
# ---------------------------------------------------------------------------


class TestClockAnchors:
    def test_tracer_anchor_maps_trace_zero_to_both_clocks(self):
        from analytics_zoo_tpu.metrics import Tracer

        t = Tracer()
        a = t.clock_anchor()
        assert abs(a["epoch"] - time.time()) < 5.0
        assert abs(a["monotonic"] - time.monotonic()) < 5.0
        assert t.to_chrome_trace()["metadata"]["clock_anchor"] == \
            pytest.approx(a)

    def test_flight_events_carry_monotonic_next_to_epoch(self):
        from analytics_zoo_tpu.metrics.flight import FlightRecorder

        fr = FlightRecorder(capacity=8)
        fr.record("step", step=1)
        doc = fr.to_doc("test")
        assert doc["reason"] == "test" and doc["pid"] == os.getpid()
        assert {"epoch", "monotonic"} <= set(doc["clock_anchor"])
        ev = doc["events"][-1]
        assert "mono" in ev and "ts" in ev
        assert abs((ev["ts"] - ev["mono"])
                   - (time.time() - time.monotonic())) < 5.0


def _flight_doc(pid, reason, events, skew_s=0.0):
    """Fabricated dump: ``skew_s`` shifts THIS process's wall clock
    while the shared monotonic clock stays truthful."""
    return {
        "reason": reason, "pid": pid, "dropped_events": 0,
        "clock_anchor": {"epoch": 1000.0 + skew_s, "monotonic": 0.0},
        "events": [dict(e, ts=1000.0 + skew_s + e["mono"])
                   for e in events],
    }


class TestFlightMerge:
    def _merge(self):
        _tools()
        import flight_merge

        return flight_merge

    def test_skewed_source_corrected_onto_cohort_clock(self):
        fm = self._merge()
        docs = [
            _flight_doc(100, "exit", [
                {"kind": "elastic", "event": "chaos", "mono": 10.0},
                {"kind": "elastic", "event": "respawn", "mono": 12.0},
            ]),
            # +5s wall-clock skew; its event REALLY happened at mono 11
            _flight_doc(200, "exit", [
                {"kind": "elastic", "event": "leave", "mono": 11.0},
            ], skew_s=5.0),
            _flight_doc(300, "exit", [
                {"kind": "elastic", "event": "join", "mono": 13.0},
            ]),
        ]
        merged = fm.merge_flight_docs(docs, skew_tolerance_s=0.25)
        assert merged["sources"] == 3
        assert merged["skew"]["200@exit"]["offset_s"] == \
            pytest.approx(5.0)
        assert merged["skew"]["200@exit"]["beyond_tolerance"] is True
        assert merged["skew"]["100@exit"]["beyond_tolerance"] is False
        # corrected ordering: chaos < leave < respawn < join
        assert [e["event"] for e in merged["timeline"]] == \
            ["chaos", "leave", "respawn", "join"]
        lines = fm.narrative_lines(merged)
        assert len(lines) == 4 and "chaos" in lines[0]

    def test_merged_chrome_trace_places_anchored_spans(self):
        fm = self._merge()
        merged = fm.merge_flight_docs([_flight_doc(100, "exit", [
            {"kind": "elastic", "event": "chaos", "mono": 10.0}])])
        trace = {"traceEvents": [
            {"name": "step", "ph": "X", "ts": 0.0, "dur": 5.0,
             "pid": 100, "tid": 1}],
            "metadata": {"clock_anchor": {"epoch": 1012.0,
                                          "monotonic": 12.0}}}
        out = fm.merged_chrome_trace(merged, [trace])
        span = [e for e in out["traceEvents"] if e["ph"] == "X"][0]
        # flight t0 = 1010.0; the span's trace-0 = epoch 1012 -> +2s
        assert span["ts"] == pytest.approx(2e6)
        assert out["metadata"]["sources"] == 1

    def test_main_returns_2_when_no_dumps(self, tmp_path):
        fm = self._merge()
        assert fm.main([str(tmp_path)]) == 2


# ---------------------------------------------------------------------------
# federated scaler path
# ---------------------------------------------------------------------------


class TestDecideFleet:
    def _hot(self):
        # est p99 = 0.12s vs a 0.1s SLO: a 1.2x proportional step
        from analytics_zoo_tpu.serving.scaler import FleetSignals

        return FleetSignals(predict_p99_s=0.12, window_count=50,
                            service_rate=10.0, queue_depth=0)

    def test_host_target_is_the_packing_consequence(self):
        from analytics_zoo_tpu.serving.scaler import SloScaler

        sc = SloScaler(slo_p99_ms=100.0, min_replicas=1,
                       max_replicas=8, up_windows=1)
        target, hosts, reason = sc.decide_fleet(2, 1, self._hot())
        assert target == 3 and reason == "slo_violation"
        assert hosts == 2  # rph = ceil(2/1) = 2 -> ceil(3/2)

    def test_explicit_packing_and_max_hosts(self):
        from analytics_zoo_tpu.serving.scaler import SloScaler

        sc = SloScaler(slo_p99_ms=100.0, min_replicas=1,
                       max_replicas=8, up_windows=1)
        target, hosts, _ = sc.decide_fleet(
            4, 2, self._hot(), replicas_per_host=1, max_hosts=3)
        assert target == 5 and hosts == 3  # capped below ceil(5/1)

    def test_idle_fleet_holds(self):
        from analytics_zoo_tpu.serving.scaler import (
            FleetSignals,
            SloScaler,
        )

        sc = SloScaler(slo_p99_ms=100.0)
        target, hosts, _ = sc.decide_fleet(2, 2, FleetSignals())
        assert (target, hosts) == (2, 2)  # rph=1: packing is kept


class _FakeBroker:
    def __init__(self, queue=7, mem=0.25):
        self._q, self._m = queue, mem

    def unclaimed(self, stream):
        return self._q

    def memory_ratio(self):
        return self._m


class TestFederatedSignalSource:
    def test_gather_assembles_fleet_signals_from_scraped_series(self):
        from analytics_zoo_tpu.serving.scaler import (
            FederatedSignalSource,
        )

        now = 1000.0
        st = TimeSeriesStore(clock=lambda: now)  # gather queries "now"
        for host in ("h1", "h2"):
            st.ingest(
                _hist_samples("zoo_serving_predict_seconds", [0.05])
                + [_counter_sample("zoo_serving_records_total", 0)],
                ts=now - 10.0, source={"host": host})
            st.ingest(
                _hist_samples("zoo_serving_predict_seconds",
                              [0.05, 0.2, 0.2])
                + [_counter_sample("zoo_serving_records_total", 20)],
                ts=now, source={"host": host})
        fed = FederatedSignalSource(st, _FakeBroker(), "s")
        sig = fed.gather(15.0)
        assert sig.window_count == 4
        assert sig.service_rate == pytest.approx(4.0)
        assert sig.queue_depth == 7
        assert sig.memory_ratio == pytest.approx(0.25)
        assert 0.1 < sig.predict_p99_s <= 0.5
        # no scraper attached: hosts = distinct stored sources
        assert fed.host_count() == 2

    def test_host_count_prefers_scraper_verdict(self):
        from analytics_zoo_tpu.serving.scaler import (
            FederatedSignalSource,
        )

        class Sc:
            def healthz(self):
                return {"targets": {"a": {"healthy": True},
                                    "b": {"healthy": False}}}

        fed = FederatedSignalSource(TimeSeriesStore(), _FakeBroker(),
                                    "s", scraper=Sc())
        assert fed.host_count() == 1


# ---------------------------------------------------------------------------
# supervisor heartbeat SLO
# ---------------------------------------------------------------------------


class TestSupervisorHeartbeatSlo:
    def test_stale_heartbeat_burns_and_logs_once_per_episode(
            self, tmp_path):
        from analytics_zoo_tpu.elastic.supervisor import TrainSupervisor

        sup = TrainSupervisor(
            "dir:" + str(tmp_path / "spool"),
            {"ckpt_dir": str(tmp_path / "ckpt")}, workers=1,
            lease_ms=800,
            hb_slo=SloSpec("worker_heartbeat",
                           "zoo_elastic_hb_age_seconds",
                           threshold=0.3, objective=0.5,
                           kind="ceiling", short_window=0.6,
                           long_window=1.2))
        # w0's training loop is wedged: hb hash stopped moving 5s ago
        sup.ledger.broker.hset(
            sup.ledger.hb_key("w0"),
            {"ts": time.time() - 5.0, "role": "spare"})
        for _ in range(9):
            sup._check_heartbeat_slo({"members": ["w0", "w9"]})
            time.sleep(0.2)
        hb = [d for d in sup.decision_log() if d["action"] == "hb_slo"]
        # fired, once per episode (not once per tick past the burn)
        assert len(hb) == 1
        d = hb[0]
        assert d["worker"] == "w0" and d["reason"] == "heartbeat_burn"
        assert d["short_burn"] >= 1.0 and d["long_burn"] >= 1.0
        # no live process to SIGTERM -> verdict logged, not killed
        assert d["verdict"] == "log"
        assert [s.name for s in sup._hb_engine.specs()] == \
            ["worker_heartbeat:w0"]  # w9 never heartbeat: no spec


# ---------------------------------------------------------------------------
# metrics_dump panels + ZooConfig knobs
# ---------------------------------------------------------------------------


class TestMetricsDumpPanels:
    def _dump(self):
        _tools()
        import metrics_dump

        return metrics_dump

    def _doc(self, firing=True):
        return {"scrape": [{
            "healthy": False, "interval": 0.5, "stale_after": 1.5,
            "targets": {"rep-0": {
                "url": "http://127.0.0.1:9090", "healthy": False,
                "age_seconds": 12.3, "fetches": 40, "errors": 3,
                "last_error": "TimeoutError('timed out')",
                "remote_healthy": None, "static": False}},
        }], "slo": [{
            "specs": [{"name": "predict_latency",
                       "family": "zoo_serving_predict_seconds",
                       "threshold": 0.08, "objective": 0.95,
                       "kind": "latency", "short_window": 1.5,
                       "long_window": 6.0, "burn_threshold": 1.0,
                       "labels": {}, "description": ""}],
            "alerts": [{"slo": "predict_latency", "firing": firing,
                        "short_burn": 2.9, "long_burn": 1.4,
                        "burn_threshold": 1.0, "threshold": 0.08,
                        "objective": 0.95, "since": 1000.0,
                        "ts": 1010.0}],
            "decisions": [{"ts": 1000.0, "slo": "predict_latency",
                           "state": "firing", "short_burn": 2.9,
                           "long_burn": 1.4}],
        }]}

    def test_render_scrape_panel(self):
        md, out = self._dump(), []
        md.render_scrape(self._doc(), out=out)
        text = "\n".join(out)
        assert "rep-0" in text and "TimeoutError" in text
        assert "healthy=False" in text or "healthy=no" in text

    def test_render_slo_panel_marks_firing(self):
        md, out = self._dump(), []
        md.render_slo(self._doc(firing=True), out=out)
        text = "\n".join(out)
        assert "predict_latency" in text and "*" in text
        md.render_slo(self._doc(firing=False), out=(out2 := []))
        assert "*predict_latency" not in "\n".join(out2)

    def test_prefix_filter_gates_panels(self):
        md = self._dump()
        md.render_scrape(self._doc(), prefix="zoo_slo", out=(o := []))
        assert o == []
        md.render_slo(self._doc(), prefix="zoo_scrape", out=(o2 := []))
        assert o2 == []


class TestZooConfigZoowatchKnobs:
    def test_defaults(self, monkeypatch):
        from analytics_zoo_tpu.common.engine import ZooConfig

        for k in list(os.environ):
            if k.startswith(("ZOO_SCRAPE", "ZOO_SLO")):
                monkeypatch.delenv(k)
        cfg = ZooConfig()
        assert cfg.scrape_targets is None
        assert cfg.scrape_interval == 1.0
        assert cfg.slo_objective == 0.99
        assert cfg.slo_short_window < cfg.slo_long_window

    @pytest.mark.parametrize("env,val", [
        ("ZOO_SLO_OBJECTIVE", "1.5"),
        ("ZOO_SLO_OBJECTIVE", "0"),
        ("ZOO_SCRAPE_INTERVAL", "0.001"),
        ("ZOO_SLO_BURN_THRESHOLD", "-1"),
        ("ZOO_SLO_SHORT_WINDOW", "600"),  # > default long 300
    ])
    def test_bad_values_rejected_eagerly_naming_the_var(
            self, monkeypatch, env, val):
        from analytics_zoo_tpu.common.engine import ZooConfig

        monkeypatch.setenv(env, val)
        with pytest.raises(ValueError) as e:
            ZooConfig()
        assert "ZOO_S" in str(e.value)


# ---------------------------------------------------------------------------
# metrics-docs drift gate
# ---------------------------------------------------------------------------


class TestMetricsDocsDrift:
    # quoted zoo_* literals that are NOT metric families
    NOT_METRICS = {
        "zoo_current_span",  # tracing contextvar name
        "zoo_export",        # ONNX export graph name
    }

    def test_every_family_in_source_is_documented(self):
        pkg = os.path.join(REPO, "analytics_zoo_tpu")
        found = set()
        for root, _, files in os.walk(pkg):
            for f in files:
                if not f.endswith(".py"):
                    continue
                with open(os.path.join(root, f)) as fh:
                    found |= set(re.findall(
                        r"""["'](zoo_[a-z0-9_]+)["']""", fh.read()))
        # trailing-underscore literals are PREFIXES (zoo_pmem_ spool
        # files, dynamic families) — not documentable family names
        families = {f for f in found
                    if not f.endswith("_")} - self.NOT_METRICS
        assert len(families) > 50  # the scan itself works
        with open(os.path.join(REPO, "docs",
                               "observability.md")) as fh:
            docs = fh.read()
        missing = sorted(f for f in families if f not in docs)
        assert not missing, (
            "metric families referenced in code but absent from "
            f"docs/observability.md: {missing} — document them (or "
            "add to NOT_METRICS if they are not metric families)")


# ---------------------------------------------------------------------------
# the two acceptance runs (heavy e2e — quick tier + nightly)
# ---------------------------------------------------------------------------


class TestFederatedAcceptance:
    def test_burn_alert_fires_before_the_hard_slo_violation(self, tmp_path):
        """A process-mode fleet's per-replica /varz is scraped; the
        scaler runs ONLY on the federated view through a 10x load step;
        the burn alert fires at /alertz before the first hard SLO
        violation window (the ISSUE 17 acceptance).  The SLO spec's
        threshold is the per-dispatch latency budget (batches filling
        up is the leading indicator of saturation), so the multi-window
        burn crosses while the client-visible p99 is still inside the
        SLO."""
        import numpy as np

        from analytics_zoo_tpu.metrics import (
            MetricsServer, VarzScraper, fleet_varz_targets)
        from analytics_zoo_tpu.serving import (
            ClusterServingHelper, InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.broker import connect_broker
        from analytics_zoo_tpu.serving.fleet import FleetController
        from analytics_zoo_tpu.serving.scaler import (
            FederatedSignalSource, SloScaler)

        service_ms = 20.0          # one replica saturates at ~50 rec/s
        slo_p99_ms = 400.0         # the HARD serving SLO (sojourn estimate)
        dispatch_budget_s = 0.08   # SLO-spec threshold: per-dispatch budget
        phases = ((8.0, 3.0), (80.0, 10.0))  # (records/s, seconds): 10x

        broker_spec = "dir:" + str(tmp_path / "spool")
        db = connect_broker(broker_spec)
        store = TimeSeriesStore(capacity=1024)
        engine = SloEngine(store, [SloSpec(
            "predict_latency", "zoo_serving_predict_seconds",
            threshold=dispatch_budget_s, objective=0.95,
            short_window=1.5, long_window=6.0, burn_threshold=1.0)])
        scraper = VarzScraper(
            store=store, engine=engine, interval=0.2, timeout=5.0,
            discover=fleet_varz_targets(db))
        srv = MetricsServer(port=0).start()  # the /alertz polled below
        ctrl = FleetController(
            ClusterServingHelper(
                model_path=None, batch_size=8, batch_budget_ms=10.0,
                lease_ms=5_000, log_dir=str(tmp_path / "logs")),
            broker_spec,
            scaler=SloScaler(slo_p99_ms=slo_p99_ms, min_replicas=1,
                             max_replicas=3, up_windows=2,
                             down_windows=10_000),
            interval=0.4, mode="process",
            signal_source=FederatedSignalSource(
                store, db, "image_stream", scraper=scraper),
            replica_metrics=True,
            replica_extra_args=("--synthetic-sleep-ms", str(service_ms)))

        marks = {"alert": None, "hard_violation": None}
        replicas_seen, hosts_seen = [1], set()
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                now = time.time()
                cur = ctrl.current()
                win = cur["window"]
                # the sojourn estimate the scaler acts on, recomputed from
                # the federated window: predict p99 + backlog drain time
                est_ms = win["predict_p99_ms"]
                if win["queue_depth"]:
                    est_ms = est_ms + (
                        win["queue_depth"] / win["service_rate"] * 1e3
                        if win["service_rate"] > 0 else float("inf"))
                if marks["hard_violation"] is None and est_ms > slo_p99_ms:
                    marks["hard_violation"] = now
                if marks["alert"] is None:
                    try:
                        with urllib.request.urlopen(
                                srv.url + "/alertz", timeout=2) as r:
                            if json.load(r).get("firing"):
                                marks["alert"] = now
                    except (OSError, ValueError):
                        pass
                replicas_seen.append(cur["replicas"])
                if cur["hosts"] is not None:
                    hosts_seen.add(cur["hosts"])
                time.sleep(0.1)

        served = {}
        outq = OutputQueue(broker=db)

        def collector():
            while not stop.is_set():
                served.update(outq.dequeue())
                time.sleep(0.01)

        scraper.start()
        ctrl.start()
        seq = 0
        try:
            # wait for discovery: the scraper must see the first replica's
            # /telemetryz before load starts (the federated view is the
            # ONLY view the scaler has)
            deadline = time.time() + 120
            while time.time() < deadline:
                hz = scraper.healthz()
                if hz["healthy"] and hz["targets"]:
                    break
                time.sleep(0.1)
            else:
                pytest.fail("scraper never discovered a replica: %r"
                            % scraper.healthz())
            threading.Thread(target=sampler, daemon=True).start()
            threading.Thread(target=collector, daemon=True).start()
            inq = InputQueue(broker=db)
            rec = np.zeros((8,), np.float32)
            for rate, duration in phases:
                t_phase = time.perf_counter()
                while time.perf_counter() - t_phase < duration:
                    inq.enqueue(f"q{seq}", rec)
                    seq += 1
                    time.sleep(1.0 / rate)
            deadline = time.time() + 240
            while len(served) < seq and time.time() < deadline:
                time.sleep(0.1)
        finally:
            stop.set()
            ctrl.stop()
            scraper.stop()
            srv.stop()

        assert ctrl.current()["federated"] is True
        assert len(scraper.healthz()["targets"]) >= 1
        assert any(d["action"] == "up" for d in ctrl.decision_log())
        assert max(replicas_seen) >= 2
        alert, hard = marks["alert"], marks["hard_violation"]
        assert alert is not None
        assert hard is None or alert <= hard  # an order of events
        assert max(hosts_seen) >= 1
        assert len(served) == seq

    def test_every_rejoin_and_respawn_has_its_cause_on_one_timeline(
            self, tmp_path):
        """A ChaosSchedule elastic run's per-process flight dumps merge
        into ONE timeline where every generation change, takeover and
        respawn has its cause event within clock-skew tolerance."""
        from analytics_zoo_tpu.elastic import ChaosSchedule, TrainSupervisor
        from analytics_zoo_tpu.metrics import get_flight_recorder

        _tools()
        import flight_merge

        flight_dir = str(tmp_path / "flight")
        spec = dict(ckpt_dir=str(tmp_path / "ckpt"), nb_epoch=3,
                    plan="dp", k=1, throttle_s=0.08)
        total_steps = (256 // 32) * spec["nb_epoch"]
        chaos = ChaosSchedule.parse(f"kill@{total_steps // 2}:w1")
        sup = TrainSupervisor(
            "dir:" + str(tmp_path / "spool"), spec, workers=3,
            lease_ms=800, min_workers=1, interval=0.1, chaos=chaos,
            worker_env={"ZOO_FLIGHT_DIR": flight_dir})
        run_start = time.time()
        res = sup.run(timeout_s=420)
        assert res is not None, sup.decision_log()
        # the supervisor's own ring is the third process-perspective
        # (workers dumped theirs on exit/SIGTERM; the SIGKILLed
        # incarnation could not — its death is explained by the
        # supervisor's chaos event instead).  Written directly so the
        # global recorder's dump-dir/once-per-reason state is untouched.
        os.makedirs(flight_dir, exist_ok=True)
        sup_doc = get_flight_recorder().to_doc("supervisor")
        # the process-global ring may hold elastic events from earlier
        # tests in this interpreter whose worker dumps are not in this
        # run's flight_dir — they would show up as uncaused effects
        sup_doc["events"] = [e for e in sup_doc["events"]
                             if e.get("ts", 0.0) >= run_start]
        with open(os.path.join(
                flight_dir, f"flight-{os.getpid()}-supervisor.json"),
                "w") as f:
            json.dump(sup_doc, f)

        merged = flight_merge.merge_flight_docs(
            flight_merge.load_inputs([flight_dir]))
        out_trace = str(tmp_path / "chaos_trace.json")
        flight_merge.write_outputs(merged, out=out_trace)

        elastic = [e for e in merged["timeline"]
                   if e.get("kind") == "elastic"]
        rejoins = [e for e in elastic if e.get("event") == "rejoin"]
        respawns = [e for e in elastic if e.get("event") == "respawn"]

        def cause_of(effect):
            """Nearest earlier event that explains `effect` — the
            chaos kill, a worker leave/join, or a respawn."""
            causes = [e for e in elastic
                      if e["t"] <= effect["t"] and e is not effect
                      and e.get("event") in ("chaos", "leave", "join",
                                             "respawn")]
            return causes[-1] if causes else None

        assert merged["sources"] >= 3
        assert any(e.get("event") == "chaos" for e in elastic)
        assert len(rejoins) >= 2
        assert [s for s, v in merged["skew"].items()
                if v["beyond_tolerance"]] == []
        for effect in rejoins + respawns:
            assert cause_of(effect) is not None, effect
        assert os.path.exists(out_trace)
