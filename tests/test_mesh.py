"""PR 18: the mesh telemetry plane — propagation, federation, SLO.

Pinned here:
  * traceparent hygiene: parse/mint reject malformed, forbidden-version
    and all-zero headers; inbound resolution ADOPTS a valid trace-id but
    always mints a fresh span-id (the daemon is a new span, not the
    caller's);
  * end-to-end propagation: a client traceparent sent to the daemon rides
    every remote-map range GET to the object store (httpstub records the
    received headers — same trace-id, never the client's span-id), comes
    back on the response and in typed error bodies, lands in the flight
    recorder and in the exported Chrome trace's otherData — and
    `parquet-tool trace-merge` stitches two processes' trace documents
    into ONE Perfetto timeline on that shared trace-id;
  * federation exactness: merged counters are byte-for-byte the
    arithmetic sum of the replica lines (integers stay integers),
    histogram buckets/sum/count add per label set, gauges are NOT summed
    (each replica keeps its sample under a replica= label), and a family
    typed differently across replicas refuses to merge;
  * SLO burn-rate: on a fake clock, an injected fault schedule drives
    ok -> burning -> ok; while burning, /healthz reports "degraded" at
    HTTP 200 (routable, deprioritized — distinct from draining's 503)
    and new scans still complete;
  * exposition goldens: every new family (io_traceparent_*, fleet_*,
    slo_*, process_*) renders with HELP + TYPE in classic Prometheus and
    OpenMetrics;
  * lane audit: every pqt-* worker pool the codebase spawns attributes to
    a named profiler lane, never "other".
"""

from __future__ import annotations

import http.client
import json
import re
import threading
import time
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from parquet_tpu.obs import fleet, propagate
from parquet_tpu.obs.prof import lane_of
from parquet_tpu.obs.slo import BurnRateEngine, SLOObjective
from parquet_tpu.serve import ScanServer, ServeConfig
from parquet_tpu.testing.httpstub import RangeHttpStub
from parquet_tpu.tools.parquet_tool import main as tool_main
from parquet_tpu.utils import metrics

WATCHDOG_S = 30.0

ROWS = 1600
ROW_GROUP = 400


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("mesh_corpus")
    t = pa.table(
        {
            "id": pa.array(np.arange(ROWS, dtype=np.int64)),
            "v": pa.array(np.linspace(0.0, 1.0, ROWS)),
        }
    )
    pq.write_table(t, str(d / "a.parquet"), row_group_size=ROW_GROUP)
    return d


def _request(server, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection(
        server.host, server.port, timeout=WATCHDOG_S
    )
    try:
        conn.request(
            method,
            path,
            body=json.dumps(body).encode() if body is not None else None,
            headers=headers or {},
        )
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


# -- traceparent hygiene -------------------------------------------------------


class TestTraceparent:
    def test_mint_parse_round_trip(self):
        ctx = propagate.mint()
        parsed = propagate.parse_traceparent(ctx.header())
        assert parsed is not None
        assert parsed.trace_id == ctx.trace_id
        assert parsed.span_id == ctx.span_id

    def test_header_shape(self):
        h = propagate.mint().header()
        assert re.fullmatch(r"00-[0-9a-f]{32}-[0-9a-f]{16}-[0-9a-f]{2}", h)

    def test_child_keeps_trace_id_fresh_span(self):
        ctx = propagate.mint()
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.span_id != ctx.span_id

    @pytest.mark.parametrize(
        "raw",
        [
            "",
            "not-a-header",
            "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace
            "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span
            "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",  # forbidden version
            "00-" + "A" * 32 + "-" + "b" * 16 + "-01",  # uppercase hex
            "00-" + "a" * 31 + "-" + "b" * 16 + "-01",  # short trace-id
            "00-" + "a" * 32 + "-" + "b" * 16 + "-01-extra" + "x" * 200,
        ],
    )
    def test_parse_rejects(self, raw):
        assert propagate.parse_traceparent(raw) is None

    def test_future_version_accepted(self):
        # per W3C: unknown (non-ff) versions parse on the 00 grammar
        got = propagate.parse_traceparent(
            "01-" + "a" * 32 + "-" + "b" * 16 + "-01"
        )
        assert got is not None and got.trace_id == "a" * 32

    def test_resolve_inbound_adopts_trace_id_mints_span(self):
        raw = "00-" + "ab" * 16 + "-" + "12" * 8 + "-01"
        ctx, outcome = propagate.resolve_inbound(raw)
        assert outcome == "accepted"
        assert ctx.trace_id == "ab" * 16
        assert ctx.span_id != "12" * 8  # the daemon is a NEW span

    def test_resolve_inbound_mints_on_absent_and_invalid(self):
        for raw, outcome in ((None, "minted"), ("garbage", "invalid")):
            ctx, got = propagate.resolve_inbound(raw)
            assert got == outcome
            assert propagate.parse_traceparent(ctx.header()) is not None

    def test_outbound_requires_scope(self):
        assert propagate.outbound_traceparent("get") is None
        ctx = propagate.mint()
        with propagate.propagation_scope(ctx):
            h = propagate.outbound_traceparent("get")
            assert h is not None
            sent = propagate.parse_traceparent(h)
            assert sent.trace_id == ctx.trace_id
            assert sent.span_id != ctx.span_id  # fresh child per call
        assert propagate.outbound_traceparent("get") is None


# -- trace-merge ---------------------------------------------------------------


def _doc(trace_id, endpoint, pid=9):
    return {
        "traceEvents": [
            {"ph": "X", "name": "s", "pid": pid, "tid": 1, "ts": 0, "dur": 2}
        ],
        "otherData": {
            "propagation": {"trace_id": trace_id},
            "request": {"endpoint": endpoint},
        },
    }


class TestTraceMerge:
    def test_merges_on_shared_trace_id(self):
        tid = "ab" * 16
        merged = propagate.merge_chrome_traces(
            [_doc(tid, "scan"), _doc(tid, "put")]
        )
        assert merged["otherData"]["propagation"]["trace_id"] == tid
        names = [
            e["args"]["name"]
            for e in merged["traceEvents"]
            if e.get("name") == "process_name"
        ]
        assert names == ["scan", "put"]
        # each input got its own pid lane
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == {0, 1}

    def test_refuses_distinct_trace_ids(self):
        with pytest.raises(ValueError, match="distinct trace ids"):
            propagate.merge_chrome_traces(
                [_doc("ab" * 16, "a"), _doc("cd" * 16, "b")]
            )

    def test_cli_round_trip(self, tmp_path):
        tid = "ef" * 16
        pa_, pb, po = (
            tmp_path / "a.json",
            tmp_path / "b.json",
            tmp_path / "m.json",
        )
        pa_.write_text(json.dumps(_doc(tid, "scan")))
        pb.write_text(json.dumps(_doc(tid, "remote")))
        rc = tool_main(["trace-merge", str(pa_), str(pb), "-o", str(po)])
        assert rc == 0
        merged = json.loads(po.read_text())
        assert merged["otherData"]["propagation"]["trace_id"] == tid
        assert len(merged["traceEvents"]) == 4  # 2 spans + 2 process names

    def test_cli_label_count_mismatch_fails(self, tmp_path, capsys):
        p = tmp_path / "a.json"
        p.write_text(json.dumps(_doc("ab" * 16, "scan")))
        rc = tool_main(["trace-merge", str(p), "--label", "x", "--label", "y"])
        assert rc == 1
        assert "one --label per input" in capsys.readouterr().err


# -- federation exactness ------------------------------------------------------

_REP_A = """\
# HELP parquet_tpu_demo_total demo counter
# TYPE parquet_tpu_demo_total counter
parquet_tpu_demo_total{op="read"} 3
parquet_tpu_demo_total{op="write"} 10
# TYPE parquet_tpu_up gauge
parquet_tpu_up 1
# TYPE parquet_tpu_lat_seconds histogram
parquet_tpu_lat_seconds_bucket{le="0.1"} 2
parquet_tpu_lat_seconds_bucket{le="+Inf"} 3
parquet_tpu_lat_seconds_sum 0.5
parquet_tpu_lat_seconds_count 3
"""

_REP_B = """\
# TYPE parquet_tpu_demo_total counter
parquet_tpu_demo_total{op="read"} 4
# TYPE parquet_tpu_up gauge
parquet_tpu_up 1
# TYPE parquet_tpu_lat_seconds histogram
parquet_tpu_lat_seconds_bucket{le="0.1"} 5
parquet_tpu_lat_seconds_bucket{le="+Inf"} 6
parquet_tpu_lat_seconds_sum 1.25
parquet_tpu_lat_seconds_count 6
"""


class TestFederationExactness:
    def test_counters_sum_byte_for_byte(self):
        merged = fleet.merge_expositions([_REP_A, _REP_B], ["r1", "r2"])
        # integer counters stay integers: 3+4=7 rendered exactly
        assert 'parquet_tpu_demo_total{op="read"} 7\n' in merged
        # a sample present on only one replica passes through unchanged
        assert 'parquet_tpu_demo_total{op="write"} 10\n' in merged

    def test_histogram_buckets_add(self):
        merged = fleet.merge_expositions([_REP_A, _REP_B], ["r1", "r2"])
        assert 'parquet_tpu_lat_seconds_bucket{le="0.1"} 7\n' in merged
        assert 'parquet_tpu_lat_seconds_bucket{le="+Inf"} 9\n' in merged
        assert "parquet_tpu_lat_seconds_sum 1.75\n" in merged
        assert "parquet_tpu_lat_seconds_count 9\n" in merged

    def test_gauges_keep_replica_label_not_summed(self):
        merged = fleet.merge_expositions([_REP_A, _REP_B], ["r1", "r2"])
        assert 'parquet_tpu_up{replica="r1"} 1\n' in merged
        assert 'parquet_tpu_up{replica="r2"} 1\n' in merged
        assert "parquet_tpu_up 2" not in merged

    def test_type_skew_refuses_to_merge(self):
        skew = _REP_B.replace(
            "# TYPE parquet_tpu_up gauge", "# TYPE parquet_tpu_up counter"
        )
        with pytest.raises(ValueError, match="deploy skew"):
            fleet.merge_expositions([_REP_A, skew], ["r1", "r2"])

    def test_merge_is_deterministic(self):
        one = fleet.merge_expositions([_REP_A, _REP_B], ["r1", "r2"])
        two = fleet.merge_expositions([_REP_A, _REP_B], ["r1", "r2"])
        assert one == two

    def test_own_render_parses_and_remerges(self):
        # the registry's own classic render (HELP before TYPE) must parse,
        # and a 2-replica self-merge must double every counter exactly
        metrics.inc("pqt_mesh_selfmerge_total", 3, op="x")
        text = metrics.render_prometheus()
        fams = fleet.parse_exposition(text)
        key = "parquet_tpu_pqt_mesh_selfmerge_total"
        assert fams[key].kind == "counter"
        merged = fleet.merge_expositions([text, text], ["r1", "r2"])
        assert 'parquet_tpu_pqt_mesh_selfmerge_total{op="x"} 6\n' in merged

    def test_normalize_peer(self):
        assert fleet.normalize_peer("127.0.0.1:8080") == (
            "http://127.0.0.1:8080/metrics"
        )
        assert fleet.normalize_peer("http://h:1/metrics") == (
            "http://h:1/metrics"
        )
        assert fleet.normalize_peer("https://h:1/") == "https://h:1/metrics"


# -- exposition goldens for the new families -----------------------------------


class TestMeshGoldens:
    def test_new_families_render_with_help_and_type(self):
        # exercise each family once so it exists in the registry
        ctx, _ = propagate.resolve_inbound(None)
        with propagate.propagation_scope(ctx):
            propagate.outbound_traceparent("get")
        BurnRateEngine(SLOObjective()).evaluate()
        fleet.federate(
            ["http://r1/metrics"], fetch=lambda url, t: _REP_A
        )
        classic = metrics.render_prometheus()
        om = metrics.render_openmetrics()
        for family, kind in [
            ("io_traceparent_injected_total", "counter"),
            ("io_traceparent_inbound_total", "counter"),
            ("fleet_scrapes_total", "counter"),
            ("fleet_replicas", "gauge"),
            ("slo_burn_rate", "gauge"),
            ("slo_error_budget_remaining", "gauge"),
            ("slo_verdict", "gauge"),
        ]:
            name = f"parquet_tpu_{family}"
            assert f"# HELP {name} " in classic, family
            assert f"# TYPE {name} {kind}" in classic, family
            om_name = (
                name[: -len("_total")]
                if kind == "counter" and name.endswith("_total")
                else name
            )
            assert f"# TYPE {om_name} {kind}" in om, family

    def test_process_self_metrics_refresh_at_render(self):
        stats = metrics.process_stats()
        text = metrics.render_prometheus()
        for family, key in [
            ("process_resident_memory_bytes", "rss_bytes"),
            ("process_open_fds", "open_fds"),
            ("process_threads_total", "threads"),
        ]:
            if key not in stats:
                continue  # non-Linux: the gauge is simply absent
            name = f"parquet_tpu_{family}"
            assert f"# TYPE {name} gauge" in text, family
            m = re.search(rf"^{re.escape(name)} (\S+)$", text, re.M)
            assert m is not None and float(m.group(1)) > 0, family

    def test_process_stats_threads_always_present(self):
        # /proc may be missing; threading.active_count() never is
        assert metrics.process_stats()["threads"] >= 1


# -- the burn-rate engine on a fake clock --------------------------------------


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class TestBurnRateEngine:
    def test_quiet_engine_is_ok(self):
        eng = BurnRateEngine(SLOObjective(), clock=_Clock())
        v = eng.evaluate()
        assert v["verdict"] == "ok"
        assert set(v["windows"]) == {"5m", "1h"}

    def test_fault_schedule_ok_burning_ok(self):
        clock = _Clock()
        eng = BurnRateEngine(
            SLOObjective(availability=0.99), clock=clock
        )
        for _ in range(100):
            eng.record(200, 0.005)
        assert eng.evaluate()["verdict"] == "ok"
        # 50% errors: burn 50x on BOTH windows (page bar is 14.4)
        for _ in range(100):
            eng.record(500, 0.005)
        v = eng.evaluate()
        assert v["verdict"] == "burning"
        assert v["burn_rates"]["availability"]["5m"] >= 14.4
        assert v["burn_rates"]["availability"]["1h"] >= 14.4
        # the schedule ends; once the slow window rolls past the burst,
        # the verdict recovers without any reset call
        clock.t += 3700.0
        for _ in range(50):
            eng.record(200, 0.005)
        assert eng.evaluate()["verdict"] == "ok"

    def test_fast_only_burn_is_warn_not_page(self):
        clock = _Clock()
        eng = BurnRateEngine(SLOObjective(availability=0.99), clock=clock)
        # seed a long clean hour so the slow window stays under the bar
        for _ in range(36):
            for _ in range(100):
                eng.record(200, 0.001)
            clock.t += 100.0
        # a short 5% burst: the fast window (300 clean + 100 here) burns
        # at 1.25x, the hour window at ~0.14x — warn territory, no page
        for _ in range(95):
            eng.record(200, 0.001)
        for _ in range(5):
            eng.record(500, 0.001)
        v = eng.evaluate()
        assert v["verdict"] == "warn"
        assert v["burn_rates"]["availability"]["5m"] >= 1.0
        assert v["burn_rates"]["availability"]["1h"] < 14.4

    def test_latency_sli_burns_when_p99_objective_set(self):
        eng = BurnRateEngine(
            SLOObjective(availability=0.999, p99_ms=10.0), clock=_Clock()
        )
        for _ in range(100):
            eng.record(200, 0.050)  # 50 ms: every request over the bar
        v = eng.evaluate()
        assert v["verdict"] == "burning"
        assert v["burn_rates"]["latency"]["5m"] >= 14.4
        assert v["windows"]["5m"]["p99_ms_estimate"] >= 10.0

    def test_no_latency_sli_without_objective(self):
        eng = BurnRateEngine(SLOObjective(), clock=_Clock())
        eng.record(200, 0.001)
        assert "latency" not in eng.evaluate()["burn_rates"]

    def test_error_status_string_counts_as_bad(self):
        eng = BurnRateEngine(SLOObjective(availability=0.99), clock=_Clock())
        for _ in range(10):
            eng.record("error", 0.001)
        assert eng.evaluate()["verdict"] == "burning"

    def test_client_errors_spend_no_budget(self):
        eng = BurnRateEngine(SLOObjective(availability=0.99), clock=_Clock())
        for _ in range(100):
            eng.record(404, 0.001)
        v = eng.evaluate()
        assert v["verdict"] == "ok"
        assert v["windows"]["5m"]["errors"] == 0

    def test_objective_validation(self):
        with pytest.raises(ValueError):
            SLOObjective(availability=1.5)
        with pytest.raises(ValueError):
            SLOObjective(p99_ms=-1.0)
        with pytest.raises(ValueError):
            SLOObjective(fast_window_s=600.0, slow_window_s=300.0)


# -- the daemon under the SLO engine (seeded chaos) ----------------------------


class TestServeSLO:
    def test_healthz_degrades_at_200_while_burning(self, corpus):
        clock = _Clock()
        eng = BurnRateEngine(SLOObjective(availability=0.99), clock=clock)
        with ScanServer(
            ServeConfig(port=0, root=str(corpus), slo_engine=eng)
        ) as server:
            server.start_background()
            status, _, body = _request(server, "GET", "/healthz")
            assert status == 200 and json.loads(body)["status"] == "ok"
            # the injected fault schedule: a 50% 5xx burst
            for _ in range(50):
                eng.record(200, 0.01)
                eng.record(503, 0.01)
            status, _, body = _request(server, "GET", "/healthz")
            doc = json.loads(body)
            # degraded is ROUTABLE: 200, not draining's 503
            assert status == 200
            assert doc["status"] == "degraded" and doc["slo"] == "burning"
            # new scans still complete while burning
            status, _, body = _request(
                server, "POST", "/v1/scan", {"paths": "a.parquet", "limit": 3}
            )
            assert status == 200 and body.count(b"\n") == 3
            # schedule over + windows rolled: the daemon recovers
            clock.t += 3700.0
            status, _, body = _request(server, "GET", "/healthz")
            assert status == 200 and json.loads(body)["status"] == "ok"

    def test_debug_slo_endpoint_shape(self, corpus):
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus),
                slo_availability=0.99, slo_p99_ms=250.0,
            )
        ) as server:
            server.start_background()
            # real traffic feeds the engine through _finish
            status, _, body = _request(
                server, "POST", "/v1/scan", {"paths": "a.parquet"}
            )
            assert status == 200, body
            # _finish runs after the response bytes flush: poll until the
            # sample lands rather than racing the handler thread
            deadline = time.time() + WATCHDOG_S
            while True:
                status, _, body = _request(server, "GET", "/v1/debug/slo")
                assert status == 200
                doc = json.loads(body)
                if doc["windows"]["5m"]["requests"] >= 1:
                    break
                assert time.time() < deadline, doc
                time.sleep(0.01)
            assert doc["verdict"] in ("ok", "warn", "burning")
            assert doc["objective"]["availability"] == 0.99
            assert doc["objective"]["p99_ms"] == 250.0
            assert doc["windows"]["5m"]["requests"] >= 1
            assert set(doc["burn_rates"]) == {"availability", "latency"}
            # the objective also rides /v1/debug/vars
            status, _, body = _request(server, "GET", "/v1/debug/vars")
            doc = json.loads(body)
            assert doc["slo"]["availability"] == 0.99
            assert doc["process"]["threads"] >= 1

    def test_bad_objective_rejected_at_config(self):
        with pytest.raises(ValueError, match="availability"):
            ServeConfig(port=0, slo_availability=2.0)


# -- end-to-end propagation ----------------------------------------------------


_CLIENT_TP = "00-" + "cafe" * 8 + "-" + "ab" * 8 + "-01"
_CLIENT_TID = "cafe" * 8


class TestServePropagation:
    def _remote_server(self, stub, corpus):
        return ScanServer(
            ServeConfig(
                port=0,
                root=str(corpus),
                remote_map={"warm": stub.base_url},
                trace_sample_rate=1.0,  # keep every span tree
            )
        )

    def test_traceparent_rides_remote_gets_and_response(self, corpus):
        data = (corpus / "a.parquet").read_bytes()
        with RangeHttpStub(files={"a.parquet": data}) as stub:
            with self._remote_server(stub, corpus) as server:
                server.start_background()
                status, headers, body = _request(
                    server,
                    "POST",
                    "/v1/scan",
                    {"paths": "warm/a.parquet", "columns": ["id"]},
                    headers={"traceparent": _CLIENT_TP},
                )
                assert status == 200, body
                # the response echoes the daemon's span on OUR trace
                echoed = propagate.parse_traceparent(headers["traceparent"])
                assert echoed.trace_id == _CLIENT_TID
                assert echoed.span_id != "ab" * 8
                # every range GET the stub served carried the trace-id,
                # each with a FRESH child span-id
                assert stub.traceparents, "no traceparent reached the stub"
                spans = set()
                for raw in stub.traceparents:
                    got = propagate.parse_traceparent(raw)
                    assert got is not None, raw
                    assert got.trace_id == _CLIENT_TID
                    assert got.span_id != "ab" * 8
                    spans.add(got.span_id)
                assert len(spans) == len(stub.traceparents)
                rid = headers["X-Request-Id"]
                status, _, body = _request(
                    server, "GET", f"/v1/debug/requests/{rid}"
                )
                assert json.loads(body)["trace_id"] == _CLIENT_TID

    def test_error_body_carries_trace_id(self, corpus):
        with ScanServer(
            ServeConfig(port=0, root=str(corpus))
        ) as server:
            server.start_background()
            status, _, body = _request(
                server,
                "POST",
                "/v1/scan",
                {"paths": "../escape.parquet"},
                headers={"traceparent": _CLIENT_TP},
            )
            assert status == 403
            assert json.loads(body)["error"]["trace_id"] == _CLIENT_TID

    def test_invalid_inbound_header_is_replaced_never_echoed(self, corpus):
        with ScanServer(
            ServeConfig(port=0, root=str(corpus))
        ) as server:
            server.start_background()
            evil = "00-" + "zz" * 16 + "-" + "ab" * 8 + "-01\r\nX-Inject: 1"
            status, headers, _ = _request(
                server,
                "POST",
                "/v1/scan",
                {"paths": "a.parquet", "limit": 1},
                headers={"traceparent": evil.replace("\r\n", " ")},
            )
            assert status == 200
            minted = propagate.parse_traceparent(headers["traceparent"])
            assert minted is not None
            assert minted.trace_id != "zz" * 16
            assert "X-Inject" not in headers

    def test_two_process_trace_merge_round_trip(self, corpus, tmp_path):
        """The acceptance pin: one client trace-id through two daemons,
        each exported Chrome trace carries it, and trace-merge stitches
        them into one document on the shared id."""
        data = (corpus / "a.parquet").read_bytes()
        docs = []
        with RangeHttpStub(files={"a.parquet": data}) as stub:
            for _ in range(2):
                with self._remote_server(stub, corpus) as server:
                    server.start_background()
                    status, headers, _ = _request(
                        server,
                        "POST",
                        "/v1/scan",
                        {"paths": "warm/a.parquet", "limit": 5},
                        headers={"traceparent": _CLIENT_TP},
                    )
                    assert status == 200
                    rid = headers["X-Request-Id"]
                    # the recorder keeps the span tree in _finish, after the
                    # response's last byte: the client can be here first
                    deadline = time.monotonic() + 5.0
                    while True:
                        status, _, body = _request(
                            server, "GET", f"/v1/debug/requests/{rid}/trace"
                        )
                        if status != 404 or time.monotonic() > deadline:
                            break
                        time.sleep(0.02)
                    assert status == 200, body
                    doc = json.loads(body)
                    assert (
                        doc["otherData"]["propagation"]["trace_id"]
                        == _CLIENT_TID
                    )
                    docs.append(doc)
        pa_, pb = tmp_path / "p0.json", tmp_path / "p1.json"
        po = tmp_path / "merged.json"
        pa_.write_text(json.dumps(docs[0]))
        pb.write_text(json.dumps(docs[1]))
        rc = tool_main(["trace-merge", str(pa_), str(pb), "-o", str(po)])
        assert rc == 0
        merged = json.loads(po.read_text())
        assert merged["otherData"]["propagation"]["trace_id"] == _CLIENT_TID
        # both processes' remote.get spans sit on the one timeline
        lanes = {e["pid"] for e in merged["traceEvents"]}
        assert lanes == {0, 1}
        names = {e.get("name") for e in merged["traceEvents"]}
        assert "remote.get" in names


# -- fleet federation over live daemons ----------------------------------------


class TestServeFleet:
    def test_fleet_smoke_two_daemons(self, corpus, tmp_path):
        """The make fleet-smoke pin: two daemons -> federated scrape via
        HTTP endpoint AND CLI -> the merged counters equal the arithmetic
        sum of the per-replica scrapes."""
        with ScanServer(ServeConfig(port=0, root=str(corpus))) as s1:
            s1.start_background()
            with ScanServer(ServeConfig(port=0, root=str(corpus))) as s2:
                s2.start_background()
                for s in (s1, s2):
                    _request(s, "POST", "/v1/scan", {"paths": "a.parquet"})
                peers = f"{s1.host}:{s1.port},{s2.host}:{s2.port}"
                texts = [
                    _request(s, "GET", "/metrics")[2].decode()
                    for s in (s1, s2)
                ]
                status, headers, body = _request(
                    s1, "GET", f"/v1/debug/fleet?peers={peers}"
                )
                assert status == 200, body
                assert headers["Content-Type"].startswith("text/plain")
                merged = body.decode()
                assert "# fleet: merged 2 replica(s)" in merged
                # exactness against the per-replica scrapes we hold
                key = re.escape(
                    'parquet_tpu_serve_requests_total{status="200",'
                    'tenant="default"}'
                )
                vals = [
                    int(re.search(rf"^{key} (\d+)$", t, re.M).group(1))
                    for t in texts
                ]
                m = re.search(rf"^{key} (\d+)$", merged, re.M)
                assert m is not None
                # scrapes raced the /metrics fetches above: the merged sum
                # can only be >= what we observed beforehand
                assert int(m.group(1)) >= sum(vals) > 0
                # gauges carry the replica label instead of summing: the
                # always-rendered uptime gauge appears once per replica
                uptimes = re.findall(
                    r'parquet_tpu_process_uptime_seconds\{replica="([^"]+)"\}',
                    merged,
                )
                assert len(uptimes) == 2 and len(set(uptimes)) == 2
        # the CLI federates the same way (daemons now closed: error path)
        rc = tool_main(["debug", "--fleet", "127.0.0.1:1"])
        assert rc == 1

    def test_fleet_endpoint_typed_errors(self, corpus):
        with ScanServer(ServeConfig(port=0, root=str(corpus))) as server:
            server.start_background()
            status, _, body = _request(server, "GET", "/v1/debug/fleet")
            assert status == 400
            assert json.loads(body)["error"]["code"] == "bad_request"
            status, _, body = _request(
                server, "GET", "/v1/debug/fleet?peers=127.0.0.1:1"
            )
            assert status == 502
            assert (
                json.loads(body)["error"]["code"] == "fleet_unreachable"
            )

    def test_debug_cli_requires_url_or_fleet(self, capsys):
        rc = tool_main(["debug"])
        assert rc == 1
        assert "daemon URL" in capsys.readouterr().err


# -- routed trace stitching over a live mesh -----------------------------------


class TestRoutedTraceMerge:
    def test_router_hop_spans_stitch_into_one_timeline(self, corpus, tmp_path):
        """The PR 19 acceptance pin: a client traceparent through the mesh
        ROUTER rides every router->replica hop as a fresh child span (the
        wire proxy records the received headers), lands in both the
        router's and the replica's flight-recorder docs, and trace-merge
        stitches the multi-process timeline on the shared trace-id."""
        from parquet_tpu.serve.mesh import MeshConfig, MeshRouter
        from parquet_tpu.testing.flaky_replica import FlakyReplica

        client_tid = "beef" * 8
        client_tp = "00-" + client_tid + "-" + "ab" * 8 + "-01"
        backend = ScanServer(
            ServeConfig(port=0, root=str(corpus))
        ).start_background()
        proxy = FlakyReplica(backend.url, seed=0).start()  # a clean wire tap
        other = ScanServer(
            ServeConfig(port=0, root=str(corpus))
        ).start_background()
        router = MeshRouter(
            MeshConfig(
                port=0,
                replicas=(proxy.url, other.url),
                trace_sample_rate=1.0,  # keep every span tree
            )
        ).start_background()
        try:
            status, headers, body = _request(
                router,
                "POST",
                "/v1/scan",
                {"paths": "a.parquet"},
                headers={"traceparent": client_tp},
            )
            assert status == 200, body
            echoed = propagate.parse_traceparent(headers["traceparent"])
            assert echoed.trace_id == client_tid
            rid_router = headers["X-Request-Id"]
            # every hop the wire tap saw is OUR trace with a FRESH span
            assert proxy.traceparents, "no hop reached the tapped replica"
            spans = set()
            for raw in proxy.traceparents:
                got = propagate.parse_traceparent(raw)
                assert got is not None, raw
                assert got.trace_id == client_tid
                assert got.span_id != "ab" * 8
                spans.add(got.span_id)
            assert len(spans) == len(proxy.traceparents)
            # the shared in-process recorder holds BOTH sides' request
            # docs under the one trace-id; pick one per side and merge
            status, _, body = _request(router, "GET", "/v1/debug/requests")
            assert status == 200
            listed = json.loads(body)["requests"]
            rids = [r["id"] for r in listed if r.get("trace_id") == client_tid]
            assert rid_router in rids
            rid_replica = next(r for r in rids if r != rid_router)
            docs = []
            for rid in (rid_router, rid_replica):
                status, _, body = _request(
                    router, "GET", f"/v1/debug/requests/{rid}/trace"
                )
                assert status == 200, body
                doc = json.loads(body)
                assert (
                    doc["otherData"]["propagation"]["trace_id"] == client_tid
                )
                docs.append(doc)
            pa_, pb = tmp_path / "router.json", tmp_path / "replica.json"
            po = tmp_path / "merged.json"
            pa_.write_text(json.dumps(docs[0]))
            pb.write_text(json.dumps(docs[1]))
            rc = tool_main(["trace-merge", str(pa_), str(pb), "-o", str(po)])
            assert rc == 0
            merged = json.loads(po.read_text())
            assert (
                merged["otherData"]["propagation"]["trace_id"] == client_tid
            )
            assert {e["pid"] for e in merged["traceEvents"]} == {0, 1}
        finally:
            router.close()
            proxy.close()
            backend.close()
            other.close()


# -- lane audit ----------------------------------------------------------------


class TestRoutedAvg:
    def test_a_routed_avg_or_decimal_sum_is_declined_typed_not_merged(self, tmp_path):
        """Scatter merges per-unit documents: a rendered avg cannot be merged
        (the average of the units' averages is another number) and a decimal
        sum comes back as text the router has no type for. Both are typed
        501 not_routable, as routed append is; a plain sum still scatters,
        and one replica answers avg itself (PR 39)."""
        from decimal import Decimal

        from parquet_tpu.serve.mesh import MeshConfig, MeshRouter

        v = [1, 1, 1, 1, 1, 2, 2, 6]
        pq.write_table(
            pa.table({"v": pa.array(v, pa.int64()),
                      "m": pa.array([Decimal(x) / 100 for x in v], pa.decimal128(9, 2))}),
            str(tmp_path / "t.parquet"), row_group_size=5, store_decimal_as_integer=True,
        )
        backends = [ScanServer(ServeConfig(port=0, root=str(tmp_path))).start_background() for _ in range(2)]
        router = MeshRouter(MeshConfig(port=0, replicas=tuple(b.url for b in backends))).start_background()
        try:
            def ask(server, aggregates):
                status, _, body = _request(server, "POST", "/v1/query", {"paths": "t.parquet", "aggregates": aggregates})
                return status, json.loads(body)

            status, doc = ask(router, ["count", "sum(v)"])
            assert status == 200 and doc["result"] == {"count": 8, "sum(v)": 15} and doc["units"] == 2
            for aggregates, named in ((["avg(v)"], "avg(v)"), (["count", "avg(m)"], "avg(m)"),
                                      (["sum(m*m)"], "sum(m*m)"), (["sum(m)"], "sum(m)")):
                status, doc = ask(router, aggregates)
                assert (status, doc["error"]["code"]) == (501, "not_routable"), doc
                assert named in doc["error"]["message"]
            status, doc = ask(backends[0], ["avg(v)", "avg(m)"])
            assert status == 200 and doc["result"] == {"avg(v)": "1.8750", "avg(m)": "0.018750"}
        finally:
            router.close()
            for b in backends:
                b.close()


class TestLaneCoverage:
    def test_every_pool_prefix_attributes_to_a_named_lane(self):
        """Grep the package for every pqt-* thread/pool name and pin that
        each attributes to a named profiler lane — a new pool added
        without a POOL_LANES entry fails here, not silently as "other"."""
        pkg = Path(__file__).resolve().parent.parent / "parquet_tpu"
        pat = re.compile(
            r"(?:thread_)?name(?:_prefix)?=f?\"(pqt-[a-z-]+)"
        )
        prefixes = set()
        for path in pkg.rglob("*.py"):
            prefixes.update(pat.findall(path.read_text()))
        assert len(prefixes) >= 10, prefixes  # the audit found the fleet
        for prefix in sorted(prefixes):
            # worker threads are named e.g. "pqt-io_3" / "pqt-serve-http"
            assert lane_of(f"{prefix}_0") != "other", prefix
            assert lane_of(prefix) != "other", prefix

    def test_lane_of_basics(self):
        assert lane_of("MainThread") == "main"
        assert lane_of("Thread-7") == "other"
        # specific lanes win over their prefixes
        assert lane_of("pqt-serve-http") == "pqt-serve-http"
        assert lane_of("pqt-serve_2") == "pqt-serve"


# -- the propagation scope rides pool hops -------------------------------------


class TestScopeAcrossPools:
    def test_instrumented_submit_carries_the_scope(self):
        from parquet_tpu.io.planner import io_pool
        from parquet_tpu.obs.pool import instrumented_submit

        ctx = propagate.mint()
        seen = []

        def probe():
            seen.append(propagate.outbound_traceparent("get"))

        with propagate.propagation_scope(ctx):
            instrumented_submit(io_pool(), probe, pool="pqt-io").result(
                timeout=WATCHDOG_S
            )
        assert seen and seen[0] is not None
        assert propagate.parse_traceparent(seen[0]).trace_id == ctx.trace_id
