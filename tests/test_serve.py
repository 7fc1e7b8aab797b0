"""parquet_tpu.serve: the concurrent scan/query daemon's contracts.

Pinned here:
  * protocol: every malformed request shape fails with a typed 400 body
    (stable `code`, never a traceback), and the JSON filter-spec parser is
    the SAME one `parquet-tool scan --filters` uses;
  * correctness: streamed jsonl and arrow-ipc responses are byte-/value-
    identical to direct FileReader scans — single client and N concurrent
    clients hammering one daemon;
  * warm-cache planning: a repeated request performs ZERO byte-source
    reads (footer + block cache hits only), asserted via io counter
    deltas;
  * admission: queue-full and tenant budgets reject with typed 429s,
    drain rejects with typed 503s, deadlines expire mid-scan as typed
    504s — and the daemon stays healthy through all of it;
  * graceful drain: SIGTERM completes the in-flight request byte-
    identically while new ones are refused;
  * chaos: a latency-spiked source (FlakySource.latency_spike) produces
    slow-but-correct responses or typed timeouts, never a hung worker or
    a torn-but-complete-looking body.

Real-sleep hammer variants are marked `slow`; the fast subset rides the
tier-1 `-m 'not slow'` run.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from parquet_tpu.core.reader import FileReader
from parquet_tpu.data.plan import build_plan
from parquet_tpu.io.cache import BlockCache, FooterCache
from parquet_tpu.io.source import LocalFileSource
from parquet_tpu.serve import ScanServer, ServeConfig, ServeError
from parquet_tpu.serve.admission import AdmissionController, Deadline
from parquet_tpu.serve.protocol import (
    filters_from_spec,
    json_default,
    parse_scan_request,
    scan_request_from_query,
)
from parquet_tpu.testing.flaky import FlakySource
from parquet_tpu.utils import metrics

WATCHDOG_S = 30.0  # every blocking wait in this file is bounded by this

ROWS_A, ROWS_B = 2400, 1800
ROW_GROUP = 800


# -- fixtures ------------------------------------------------------------------


def _write_corpus(d):
    """Two files, several row groups each, ids globally sorted so min/max
    statistics can prune whole groups."""
    rng = np.random.default_rng(7)
    rows = {"a.parquet": (0, ROWS_A), "b.parquet": (ROWS_A, ROWS_B)}
    for name, (base, n) in rows.items():
        t = pa.table(
            {
                "id": pa.array(np.arange(base, base + n, dtype=np.int64)),
                "v": pa.array(rng.standard_normal(n).astype(np.float64)),
                "name": pa.array([f"n{i % 13}" for i in range(n)]),
            }
        )
        pq.write_table(t, str(d / name), row_group_size=ROW_GROUP)
    return d


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    return _write_corpus(tmp_path_factory.mktemp("serve_corpus"))


@pytest.fixture()
def server(corpus):
    with ScanServer(ServeConfig(port=0, root=str(corpus), cache_mb=32)) as s:
        s.start_background()
        yield s


def _request(
    server,
    method,
    path,
    body=None,
    headers=None,
    timeout=WATCHDOG_S,
):
    """One HTTP exchange with a hard socket timeout (a hang fails the test
    instead of wedging the run). Returns (status, headers, body_bytes)."""
    conn = http.client.HTTPConnection(server.host, server.port, timeout=timeout)
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


def _scan(server, body, headers=None, timeout=WATCHDOG_S):
    return _request(server, "POST", "/v1/scan", body, headers, timeout)


def _expected_jsonl(corpus, names, columns=None, filters=None, limit=None):
    """The daemon contract: rows of every file in sorted path order,
    serialized exactly as the executor does."""
    out = []
    n = 0
    for name in sorted(names):
        with FileReader(str(corpus / name), columns=columns) as r:
            for row in r.iter_rows(filters=filters):
                out.append(json.dumps(row, default=json_default) + "\n")
                n += 1
                if limit is not None and n >= limit:
                    return "".join(out).encode()
    return "".join(out).encode()


def _settled_delta(snap, key: str, *, want: int = 1, timeout_s: float = 5.0):
    """metrics.delta(snap) once `key` reaches `want`. The handler finishes
    a request (counter + SLI sample + flight-record close) AFTER the
    response bytes flush, so a delta taken the instant the client reads
    the body can race it — poll briefly, then assert on the settled view."""
    deadline = time.time() + timeout_s
    while True:
        d = metrics.delta(snap)
        if d.get(key, 0) >= want or time.time() >= deadline:
            return d
        time.sleep(0.002)


def _settled_record(server, rid: str, *, timeout_s: float = 5.0):
    """GET /v1/debug/requests/<rid> once the record has CLOSED — the
    recorder's finish step runs after the response flushes, the same race
    _settled_delta absorbs. Returns (status, doc)."""
    deadline = time.time() + timeout_s
    while True:
        s, _h, b = _request(server, "GET", f"/v1/debug/requests/{rid}")
        doc = json.loads(b)
        if s != 200 or doc.get("open") is False or time.time() >= deadline:
            return s, doc
        time.sleep(0.002)


def _settled_in_flight(server, *, timeout_s: float = 5.0) -> int:
    """admission.in_flight once it has fallen to 0: a request's slot is
    released after its response flushes, the same race _settled_delta
    absorbs."""
    deadline = time.time() + timeout_s
    while server.service.admission.in_flight and time.time() < deadline:
        time.sleep(0.002)
    return server.service.admission.in_flight


def _error_code(body: bytes) -> str:
    doc = json.loads(body)
    assert set(doc) == {"error"}, doc
    # request_id and trace_id ride every error body produced inside a
    # recorded request (the correlation keys for /v1/debug/requests and
    # cross-process trace-merge); pre-record errors (bad route, oversized
    # body) legitimately lack them
    extra = {"request_id", "trace_id"}
    assert set(doc["error"]) - extra == {"code", "message", "status"}, doc
    rid = doc["error"].get("request_id")
    if rid is not None:
        assert isinstance(rid, str) and 0 < len(rid) <= 64, doc
    tid = doc["error"].get("trace_id")
    if tid is not None:
        assert isinstance(tid, str) and len(tid) == 32, doc
    return doc["error"]["code"]


class _GatedSource:
    """A ByteSource whose data reads block until the test opens the gate —
    the deterministic way to hold a request in flight."""

    def __init__(self, path, gate):
        self._inner = LocalFileSource(path)
        self._gate = gate

    @property
    def source_id(self):
        return self._inner.source_id

    def size(self):
        return self._inner.size()

    def read_at(self, offset, n):
        assert self._gate.wait(WATCHDOG_S), "test gate never opened"
        return self._inner.read_at(offset, n)

    def read_ranges(self, ranges):
        return [self.read_at(o, n) for o, n in ranges]

    def close(self):
        self._inner.close()


# -- protocol ------------------------------------------------------------------


class TestProtocol:
    def test_filter_spec_shapes(self):
        assert filters_from_spec(None) is None
        assert filters_from_spec([]) is None
        assert filters_from_spec([["id", "<", 3]]) == [("id", "<", 3)]
        dnf = filters_from_spec([[["id", "<", 3]], [["id", ">=", 9]]])
        assert dnf == [[("id", "<", 3)], [("id", ">=", 9)]]

    @pytest.mark.parametrize(
        "spec",
        [
            "id < 3",  # not a list
            [["id"]],  # not a triple
            [[3, "<", 3]],  # column not a string
            [["id", "~", 3]],  # unknown op
        ],
    )
    def test_filter_spec_rejections(self, spec):
        with pytest.raises(ServeError) as ei:
            filters_from_spec(spec)
        assert ei.value.status == 400
        assert ei.value.code == "bad_filters"

    @pytest.mark.parametrize(
        "raw,code",
        [
            (b"", "bad_request"),
            (b"not json", "bad_request"),
            (b"[1,2]", "bad_request"),
            (b'{"paths": []}', "bad_request"),
            (b'{"paths": "a", "nope": 1}', "bad_request"),
            (b'{"paths": "a", "limit": -1}', "bad_request"),
            (b'{"paths": "a", "limit": true}', "bad_request"),
            (b'{"paths": "a", "format": "csv"}', "bad_request"),
            (b'{"paths": "a", "shard": [2, 2]}', "bad_request"),
            (b'{"paths": "a", "timeout_ms": 0}', "bad_request"),
            (b'{"paths": "a", "filters": [["id", "~", 1]]}', "bad_filters"),
        ],
    )
    def test_parse_rejections_are_typed(self, raw, code):
        with pytest.raises(ServeError) as ei:
            parse_scan_request(raw)
        assert ei.value.status == 400
        assert ei.value.code == code
        assert _error_code(json.dumps(ei.value.to_body()).encode()) == code

    def test_parse_accepts_full_request(self):
        req = parse_scan_request(
            json.dumps(
                {
                    "paths": "a.parquet",
                    "columns": "id,v",
                    "filters": [["id", "<", 10]],
                    "limit": 5,
                    "format": "arrow-ipc",
                    "shard": "1/2",
                    "timeout_ms": 1000,
                }
            ).encode()
        )
        assert req.paths == ["a.parquet"]
        assert req.columns == ["id", "v"]
        assert req.filters == [("id", "<", 10)]
        assert req.limit == 5 and req.format == "arrow-ipc"
        assert req.shard == (1, 2) and req.timeout_ms == 1000

    def test_query_request(self):
        req = scan_request_from_query(
            {
                "paths": ["a.parquet,b.parquet"],
                "columns": ["id"],
                "filters": ['[["id", ">=", 7]]'],
                "limit": ["3"],
                "shard": ["0/2"],
            }
        )
        assert req.paths == ["a.parquet", "b.parquet"]
        assert req.columns == ["id"]
        assert req.filters == [("id", ">=", 7)]
        assert req.limit == 3 and req.shard == (0, 2)
        with pytest.raises(ServeError):
            scan_request_from_query({})


# -- admission (clock-injected unit level) -------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestAdmission:
    def test_queue_full_and_release(self):
        a = AdmissionController(max_inflight=2)
        t1, t2 = a.admit("x"), a.admit("y")
        with pytest.raises(ServeError) as ei:
            a.admit("z")
        assert ei.value.status == 429 and ei.value.code == "queue_full"
        t1.release()
        t1.release()  # idempotent
        a.admit("z").release()
        t2.release()
        assert a.in_flight == 0

    def test_tenant_concurrency_is_per_tenant(self):
        a = AdmissionController(max_inflight=10, tenant_concurrent=1)
        t = a.admit("alice")
        with pytest.raises(ServeError) as ei:
            a.admit("alice")
        assert ei.value.code == "tenant_concurrency"
        a.admit("bob").release()  # other tenants unaffected
        t.release()
        a.admit("alice").release()

    def test_tenant_budget_token_bucket(self):
        clock = _FakeClock()
        a = AdmissionController(
            tenant_budget_bytes=1000, budget_window_s=10.0, clock=clock
        )
        a.charge("t", 600)
        with pytest.raises(ServeError) as ei:
            a.charge("t", 600)  # 400 left
        assert ei.value.status == 429
        assert ei.value.code == "tenant_over_budget"
        assert ei.value.retry_after_s >= 1
        clock.t += 2.0  # +200 tokens
        a.charge("t", 600)
        # a full bucket admits one oversized scan rather than never serving it
        clock.t += 100.0
        a.charge("t", 5000)
        with pytest.raises(ServeError):
            a.charge("t", 1)

    def test_deadline(self):
        clock = _FakeClock()
        d = Deadline(5.0, clock=clock)
        d.check()
        assert d.remaining() == 5.0
        clock.t = 5.0
        with pytest.raises(ServeError) as ei:
            d.check()
        assert ei.value.status == 504 and ei.value.code == "deadline_exceeded"
        assert Deadline(None, clock=clock).remaining() is None

    def test_tenant_table_is_bounded(self):
        overflow = AdmissionController.OVERFLOW_TENANT
        a = AdmissionController(max_tenants=2, tenant_concurrent=4)
        # the label set saturates at max_tenants for the LIFE of the
        # process — a flood of distinct X-Tenant values (on any endpoint,
        # admitted or not) cannot grow memory or the metrics label set
        assert a.resolve_tenant("x") == "x"
        assert a.resolve_tenant("y") == "y"
        assert a.resolve_tenant("z") == overflow
        assert a.resolve_tenant("w") == overflow
        assert a.resolve_tenant("x") == "x"  # known names keep their key
        # sanitization: empty/whitespace -> "default", long names truncated
        assert a.resolve_tenant(None) == overflow  # set already saturated
        b = AdmissionController(max_tenants=8)
        assert b.resolve_tenant(None) == "default"
        assert b.resolve_tenant("  ") == "default"
        assert len(b.resolve_tenant("q" * 200)) == 64
        # the per-tenant STATE table is bounded too: full + all active ->
        # overflow bucket; an idle tenant is evicted to make room
        t1, t2 = a.admit("x"), a.admit("y")
        t3 = a.admit("z")
        assert t3.tenant == overflow
        t1.release()
        t3.release()
        t4 = a.admit("v")
        assert t4.tenant == "v"  # "x" (idle) was evicted
        assert len(a._tenants) <= 3  # y/v + at most the overflow bucket
        for t in (t2, t4):
            t.release()

    def test_drain_semantics(self):
        a = AdmissionController()
        t = a.admit("x")
        a.begin_drain()
        with pytest.raises(ServeError) as ei:
            a.admit("y")
        assert ei.value.status == 503 and ei.value.code == "draining"
        assert a.wait_drained(timeout=0.05) is False
        t.release()
        assert a.wait_drained(timeout=WATCHDOG_S) is True


# -- plan: pruning summary + push-down -----------------------------------------


class TestPlan:
    def test_build_plan_pruning_summary(self, corpus):
        paths = str(corpus / "*.parquet")
        plan = build_plan(paths)
        total = -(-ROWS_A // ROW_GROUP) + -(-ROWS_B // ROW_GROUP)
        assert plan.pruning_summary() == {
            "units_total": total,
            "units_pruned_stats": 0,
            "units_pruned_bloom": 0,
            "units_admitted": total,
        }
        plan = build_plan(paths, filters=[("id", "<", ROW_GROUP)])
        assert plan.units_total == total
        assert plan.units_pruned_stats == total - 1
        assert plan.num_units == 1
        assert (
            plan.units_total
            - plan.units_pruned_stats
            - plan.units_pruned_bloom
            == plan.num_units
        )

    def test_plan_endpoint_reports_pruning(self, server):
        flt = json.dumps([["id", "<", ROW_GROUP]])
        status, _h, body = _request(
            server,
            "GET",
            "/v1/plan?paths=a.parquet,b.parquet&filters=" + flt.replace(" ", ""),
        )
        assert status == 200
        doc = json.loads(body)
        assert doc["units_admitted"] == doc["units"] == 1
        assert doc["units_pruned_stats"] == doc["units_total"] - 1
        assert doc["rows"] == ROW_GROUP
        assert doc["estimated_bytes"] > 0

    def test_plan_post_matches_get(self, server):
        _s, _h, via_get = _request(server, "GET", "/v1/plan?paths=a.parquet")
        _s, _h, via_post = _request(
            server, "POST", "/v1/plan", {"paths": "a.parquet"}
        )
        assert via_get == via_post

    def test_warm_plan_zero_source_reads(self, server):
        flt = json.dumps([["id", "<", 100]]).replace(" ", "")
        path = "/v1/plan?paths=a.parquet,b.parquet&filters=" + flt
        _request(server, "GET", path)  # cold: parses footers
        s0 = metrics.snapshot()
        status, _h, _b = _request(server, "GET", path)
        d = metrics.delta(s0)
        assert status == 200
        assert d.get("io_bytes_read_total", 0) == 0
        assert d.get("io_read_calls_total", 0) == 0
        assert d.get("io_footer_cache_hits_total", 0) >= 2

    def test_bloom_pruning_counted_and_cached(self, tmp_path):
        from parquet_tpu.core.writer import FileWriter as PqtWriter
        from parquet_tpu.schema.dsl import parse_schema

        path = str(tmp_path / "bloomed.parquet")
        schema = parse_schema("message m { required binary s (UTF8); }")
        with PqtWriter(path, schema, bloom_filters=["s"]) as w:
            for part in (
                [f"k{i}" for i in range(500)],
                [f"k{i}" for i in range(500, 1000)],
            ):
                w.write_column("s", part)
                w.flush_row_group()
        # stats cannot prune "zzz" (within k0..k999 lexically? no: > k999)
        # so probe a value INSIDE the min/max range that no group contains
        fc, bc = FooterCache(), BlockCache(8 << 20)
        plan = build_plan(
            path,
            filters=[("s", "==", "k499x")],
            footer_cache=fc,
            block_cache=bc,
        )
        assert plan.num_units == 0
        assert plan.units_pruned_bloom + plan.units_pruned_stats == 2
        assert plan.units_pruned_bloom >= 1
        # warm re-plan: bloom pages come from the block cache, footers from
        # the footer cache — zero source reads
        s0 = metrics.snapshot()
        plan2 = build_plan(
            path,
            filters=[("s", "==", "k499x")],
            footer_cache=fc,
            block_cache=bc,
        )
        d = metrics.delta(s0)
        assert plan2.pruning_summary() == plan.pruning_summary()
        assert d.get("io_bytes_read_total", 0) == 0


# -- scan correctness ----------------------------------------------------------


class TestScanCorrectness:
    def test_jsonl_matches_filereader(self, server, corpus):
        status, headers, body = _scan(
            server, {"paths": ["a.parquet", "b.parquet"]}
        )
        assert status == 200
        assert headers["Content-Type"] == "application/x-ndjson"
        assert body == _expected_jsonl(corpus, ["a.parquet", "b.parquet"])

    def test_glob_columns_filters_limit(self, server, corpus):
        body_spec = {
            "paths": "*.parquet",
            "columns": ["id", "name"],
            "filters": [["id", ">=", ROWS_A - 5]],
            "limit": 8,
        }
        status, _h, body = _scan(server, body_spec)
        assert status == 200
        assert body == _expected_jsonl(
            corpus,
            ["a.parquet", "b.parquet"],
            columns=["id", "name"],
            filters=[("id", ">=", ROWS_A - 5)],
            limit=8,
        )

    def test_arrow_ipc_matches_to_arrow(self, server, corpus):
        status, headers, body = _scan(
            server, {"paths": "a.parquet", "format": "arrow-ipc"}
        )
        assert status == 200
        assert headers["Content-Type"] == "application/vnd.apache.arrow.stream"
        got = pa.ipc.open_stream(body).read_all()
        with FileReader(str(corpus / "a.parquet")) as r:
            want = r.to_arrow()
        assert got.equals(want)

    def test_arrow_ipc_empty_result_is_valid_stream(self, server):
        status, _h, body = _scan(
            server,
            {
                "paths": "a.parquet",
                "format": "arrow-ipc",
                "filters": [["id", "<", -1]],
            },
        )
        assert status == 200
        got = pa.ipc.open_stream(body).read_all()
        assert got.num_rows == 0 and "id" in got.column_names

    def test_shard_request_partitions_corpus(self, server, corpus):
        parts = []
        for i in (0, 1):
            status, _h, body = _scan(
                server, {"paths": "*.parquet", "shard": [i, 2]}
            )
            assert status == 200
            parts.append(body)
        whole = _expected_jsonl(corpus, ["a.parquet", "b.parquet"])
        got_ids = sorted(
            json.loads(ln)["id"]
            for part in parts
            for ln in part.decode().splitlines()
        )
        want_ids = [
            json.loads(ln)["id"] for ln in whole.decode().splitlines()
        ]
        assert got_ids == want_ids  # every row exactly once across shards

    def test_request_errors_are_typed(self, server):
        for body_spec, status, code in [
            ({"paths": "missing.parquet"}, 404, "not_found"),
            ({"paths": "../etc/passwd"}, 403, "path_outside_root"),
            ({"paths": "/etc/passwd"}, 403, "path_outside_root"),
            ({"paths": "a.parquet", "columns": ["nope"]}, 400, "bad_columns"),
            (
                {"paths": "a.parquet", "filters": [["nope", "<", 1]]},
                400,
                "bad_request",
            ),
        ]:
            s, _h, b = _scan(server, body_spec)
            assert (s, _error_code(b)) == (status, code), body_spec
        s, _h, b = _request(server, "GET", "/v1/nope")
        assert s == 404 and _error_code(b) == "no_such_route"

    def test_warm_scan_zero_source_reads(self, server, corpus):
        spec = {"paths": "a.parquet", "columns": ["id", "v"]}
        cold = _scan(server, spec)[2]  # populates footer + block caches
        s0 = metrics.snapshot()
        status, _h, warm = _scan(server, spec)
        d = metrics.delta(s0)
        assert status == 200 and warm == cold
        assert d.get("io_bytes_read_total", 0) == 0
        assert d.get("io_read_calls_total", 0) == 0
        assert d.get("io_cache_hits_total", 0) > 0

    def test_concurrent_clients_byte_identical(self, server, corpus):
        want = _expected_jsonl(corpus, ["a.parquet", "b.parquet"])
        n_threads, per_thread = 8, 2
        results: dict[int, list] = {i: [] for i in range(n_threads)}
        errors: list = []

        def hammer(i):
            try:
                for _ in range(per_thread):
                    status, _h, body = _scan(server, {"paths": "*.parquet"})
                    results[i].append((status, body))
            except Exception as e:  # noqa: BLE001 - collected for the assert
                errors.append(e)

        threads = [
            threading.Thread(target=hammer, args=(i,)) for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WATCHDOG_S)
            assert not t.is_alive(), "hammer thread hung"
        assert not errors
        for i in range(n_threads):
            assert len(results[i]) == per_thread
            for status, body in results[i]:
                assert status == 200 and body == want

    def test_internal_bugs_render_typed_500(self, server):
        # a NON-ServeError escaping a handler is a bug, but the client
        # still sees the structured 500 body — never a traceback — and the
        # request is counted exactly once
        def boom(request):
            raise RuntimeError("wat")

        orig = server.service.session.plan
        server.service.session.plan = boom
        try:
            s0 = metrics.snapshot()
            status, _h, body = _scan(server, {"paths": "a.parquet"})
            d = _settled_delta(
                s0, 'serve_requests_total{status="500",tenant="default"}'
            )
            assert status == 500 and _error_code(body) == "internal"
            assert b"Traceback" not in body
            counted = [
                (k, v)
                for k, v in d.items()
                if k.startswith("serve_requests_total")
            ]
            assert counted == [
                ('serve_requests_total{status="500",tenant="default"}', 1)
            ]
        finally:
            server.service.session.plan = orig
        assert _scan(server, {"paths": "a.parquet", "limit": 1})[0] == 200

    def test_metrics_and_healthz(self, server):
        _scan(server, {"paths": "a.parquet", "limit": 1})
        s, _h, body = _request(server, "GET", "/metrics")
        text = body.decode()
        assert s == 200
        assert "parquet_tpu_serve_requests_total" in text
        assert "parquet_tpu_serve_queue_depth" in text
        assert "parquet_tpu_serve_request_seconds" in text
        assert "parquet_tpu_serve_scan_bytes_total" in text
        s, _h, body = _request(server, "GET", "/healthz")
        assert s == 200 and json.loads(body)["status"] == "ok"


# -- admission through HTTP ----------------------------------------------------


class TestAdmissionHTTP:
    def _gated_server(self, corpus, gate, **cfg):
        config = ServeConfig(
            port=0,
            root=str(corpus),
            cache_mb=0,
            source_factory=lambda p: _GatedSource(p, gate),
            **cfg,
        )
        return ScanServer(config)

    def _hold_one(self, server, errors):
        """Start a scan that blocks on the gate; returns its thread and a
        slot the response lands in."""
        out = {}

        def go():
            try:
                out["resp"] = _scan(server, {"paths": "a.parquet"})
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        t = threading.Thread(target=go)
        t.start()
        deadline = time.monotonic() + WATCHDOG_S
        while server.service.admission.in_flight < 1:
            assert time.monotonic() < deadline, "request never admitted"
            time.sleep(0.005)
        return t, out

    def test_queue_full_429_then_recovers(self, corpus):
        gate = threading.Event()
        errors: list = []
        with self._gated_server(corpus, gate, max_inflight=1) as server:
            server.start_background()
            t, out = self._hold_one(server, errors)
            status, headers, body = _scan(server, {"paths": "a.parquet"})
            assert status == 429 and _error_code(body) == "queue_full"
            assert "Retry-After" in headers
            gate.set()
            t.join(WATCHDOG_S)
            assert not t.is_alive() and not errors
            assert out["resp"][0] == 200
            # slot freed: the same request now succeeds
            assert _scan(server, {"paths": "a.parquet"})[0] == 200

    def test_tenant_budget_exhaustion_429(self, corpus):
        # budget 1 MiB/window; one a.parquet scan estimate is ~tens of KiB,
        # so the FIRST drains the warm bucket and the SECOND is refused
        with ScanServer(
            ServeConfig(
                port=0,
                root=str(corpus),
                tenant_budget_mb=1,
                budget_window_s=3600.0,
            )
        ) as server:
            server.start_background()
            est = json.loads(
                _request(server, "GET", "/v1/plan?paths=a.parquet")[2]
            )["estimated_bytes"]
            assert est > 0
            headers = {"X-Tenant": "alice"}
            for _ in range((1 << 20) // est + 1):
                status, _h, body = _scan(
                    server, {"paths": "a.parquet", "limit": 1}, headers
                )
                if status != 200:
                    break
            assert status == 429 and _error_code(body) == "tenant_over_budget"
            # budgets are per tenant: bob is unaffected
            s2 = _scan(server, {"paths": "a.parquet", "limit": 1}, {"X-Tenant": "bob"})
            assert s2[0] == 200

    def test_deadline_expiry_mid_scan_leaves_daemon_healthy(self, corpus):
        slow = lambda p: FlakySource(  # noqa: E731
            LocalFileSource(p), seed=0, latency_s=0.25
        )
        with ScanServer(
            ServeConfig(port=0, root=str(corpus), cache_mb=0, source_factory=slow)
        ) as server:
            server.start_background()
            status, _h, body = _scan(
                server,
                {"paths": "*.parquet"},
                headers={"X-Timeout-Ms": "120"},
            )
            assert status == 504 and _error_code(body) == "deadline_exceeded"
            # the daemon is fine: healthy, and an unhurried scan completes
            assert _request(server, "GET", "/healthz")[0] == 200
            status, _h, body = _scan(server, {"paths": "a.parquet", "limit": 2})
            assert status == 200 and body.count(b"\n") == 2
            assert _settled_in_flight(server) == 0

    def test_stalled_client_frees_thread_and_slot(self, corpus):
        """A client that sends headers and then stalls (never the body, or
        never reads the response) must not pin a handler thread forever:
        the socket timeout tears the connection down and the daemon stays
        fully available."""
        import socket

        with ScanServer(
            ServeConfig(port=0, root=str(corpus), socket_timeout_s=0.3)
        ) as server:
            server.start_background()
            stalled = socket.create_connection(
                (server.host, server.port), timeout=WATCHDOG_S
            )
            try:
                # promise a body, never send it: the handler blocks in
                # _read_body until the socket timeout frees it
                stalled.sendall(
                    b"POST /v1/scan HTTP/1.1\r\n"
                    b"Host: x\r\nContent-Length: 100\r\n\r\n"
                )
                deadline = time.monotonic() + WATCHDOG_S
                stalled.settimeout(WATCHDOG_S)
                while True:
                    assert time.monotonic() < deadline, "stall never torn down"
                    if stalled.recv(4096) == b"":
                        break  # server closed the stalled connection
            finally:
                stalled.close()
            # the daemon is healthy and no admission slot leaked
            assert server.service.admission.in_flight == 0
            assert _scan(server, {"paths": "a.parquet", "limit": 1})[0] == 200

    def test_graceful_drain_on_sigterm(self, corpus):
        gate = threading.Event()
        errors: list = []
        prev_term = signal.getsignal(signal.SIGTERM)
        prev_int = signal.getsignal(signal.SIGINT)
        server = self._gated_server(corpus, gate)
        try:
            server.start_background()
            server.install_signal_handlers()
            t, out = self._hold_one(server, errors)
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + WATCHDOG_S
            while not server.service.admission.draining:
                assert time.monotonic() < deadline, "SIGTERM never drained"
                time.sleep(0.005)
            # new requests refused with the typed 503 while draining
            status, _h, body = _scan(server, {"paths": "a.parquet"})
            assert status == 503 and _error_code(body) == "draining"
            s, _h, body = _request(server, "GET", "/healthz")
            assert s == 503 and json.loads(body)["status"] == "draining"
            # ... but the in-flight request runs to byte-identical completion
            gate.set()
            t.join(WATCHDOG_S)
            assert not t.is_alive() and not errors
            status, _h, body = out["resp"]
            assert status == 200
            assert body == _expected_jsonl(corpus, ["a.parquet"])
        finally:
            signal.signal(signal.SIGTERM, prev_term)
            signal.signal(signal.SIGINT, prev_int)
            server.close()


# -- failure streaming ---------------------------------------------------------


class TestTornStream:
    def test_mid_stream_corruption_tears_the_response(self, tmp_path):
        # file ordering puts the corrupt file SECOND, so the first unit
        # streams (200 sent) before the decode error surfaces
        d = _write_corpus(tmp_path)
        bad = d / "b.parquet"
        raw = bytearray(bad.read_bytes())
        # stomp every page header of the first row group (the bytes right
        # after the magic): decode MUST fail, footer stays parseable
        raw[4:2048] = b"\xde" * 2044
        bad.write_bytes(bytes(raw))
        with ScanServer(ServeConfig(port=0, root=str(d), window=1)) as server:
            server.start_background()
            conn = http.client.HTTPConnection(
                server.host, server.port, timeout=WATCHDOG_S
            )
            try:
                conn.request(
                    "POST",
                    "/v1/scan",
                    body=json.dumps({"paths": "*.parquet"}).encode(),
                )
                resp = conn.getresponse()
                assert resp.status == 200
                with pytest.raises(http.client.IncompleteRead) as ei:
                    resp.read()
                partial = ei.value.partial
            finally:
                conn.close()
            # the body carries a typed terminal error record, and the torn
            # chunked encoding is DETECTABLE (no terminating 0-chunk)
            last = partial.decode().splitlines()[-1]
            assert json.loads(last)["error"]["code"] == "unreadable_file"
            # the daemon survives and still serves the healthy file
            status, _h, body = _scan(server, {"paths": "a.parquet", "limit": 1})
            assert status == 200

    def test_routed_mid_stream_corruption_tears_the_same_way(self, tmp_path):
        """The PR 19 extension of the pin above: through the mesh ROUTER,
        a corrupt second file still yields a detectably torn stream whose
        terminal record is the replica's typed error — the healthy file's
        units stream first, the corrupt unit's typed 422 (sent by every
        replica BEFORE its 200) surfaces mid-stream, and the router never
        fabricates a clean end-of-stream."""
        from parquet_tpu.serve.mesh import MeshConfig, MeshRouter

        d = _write_corpus(tmp_path)
        bad = d / "b.parquet"
        raw = bytearray(bad.read_bytes())
        raw[4:2048] = b"\xde" * 2044
        bad.write_bytes(bytes(raw))
        replicas = [
            ScanServer(
                ServeConfig(port=0, root=str(d), window=1)
            ).start_background()
            for _ in range(3)
        ]
        router = MeshRouter(
            MeshConfig(port=0, replicas=tuple(r.url for r in replicas))
        ).start_background()
        try:
            conn = http.client.HTTPConnection(
                router.host, router.port, timeout=WATCHDOG_S
            )
            try:
                conn.request(
                    "POST",
                    "/v1/scan",
                    body=json.dumps({"paths": "*.parquet"}).encode(),
                )
                resp = conn.getresponse()
                assert resp.status == 200
                with pytest.raises(http.client.IncompleteRead) as ei:
                    resp.read()
                partial = ei.value.partial
            finally:
                conn.close()
            lines = partial.decode().splitlines()
            # the healthy file streamed before the tear...
            assert len(lines) > 1
            # ...and the terminal record is the replica's typed error
            assert json.loads(lines[-1])["error"]["code"] == "unreadable_file"
            # the router survives and still serves the healthy file
            status, _h, body = _request(
                router, "POST", "/v1/scan", {"paths": "a.parquet", "limit": 1}
            )
            assert status == 200
        finally:
            router.close()
            for s in replicas:
                s.close()


# -- chaos: the latency-spiked source ------------------------------------------


class TestLatencySpikes:
    def test_flaky_latency_spike_preset(self):
        data = b"0123456789" * 100
        from parquet_tpu.io.source import MemorySource

        sleeps: list = []
        src = FlakySource.latency_spike(
            MemorySource(data), seed=3, p=0.5, ms=40.0, sleep=sleeps.append
        )
        got = [src.read_at(i * 10, 10) for i in range(50)]
        assert got == [data[i * 10 : i * 10 + 10] for i in range(50)]
        assert 0 < src.spikes_injected < 50
        assert sleeps == [0.04] * src.spikes_injected
        # seeded: the same seed replays the same spike schedule
        src2 = FlakySource.latency_spike(
            MemorySource(data), seed=3, p=0.5, ms=40.0, sleep=lambda s: None
        )
        for i in range(50):
            src2.read_at(i * 10, 10)
        assert src2.spikes_injected == src.spikes_injected

    def test_spiked_source_slow_or_typed_timeout_never_hung(self, corpus):
        spiky = lambda p: FlakySource.latency_spike(  # noqa: E731
            LocalFileSource(p), seed=11, p=0.3, ms=20.0
        )
        with ScanServer(
            ServeConfig(port=0, root=str(corpus), cache_mb=0, source_factory=spiky)
        ) as server:
            server.start_background()
            want = _expected_jsonl(corpus, ["a.parquet"])
            # generous deadline: spikes slow the response but bytes are right
            for _ in range(3):
                status, _h, body = _scan(server, {"paths": "a.parquet"})
                assert status == 200 and body == want
            # hostile deadline: a clean typed 504, a clean completion, or a
            # DETECTABLY torn stream whose terminal record is the typed
            # deadline error (the deadline fired after the 200 went out) —
            # and the worker slot is always released, never a hung worker
            for _ in range(4):
                try:
                    status, _h, body = _scan(
                        server,
                        {"paths": "*.parquet"},
                        headers={"X-Timeout-Ms": "40"},
                    )
                except http.client.IncompleteRead as e:
                    last = e.partial.decode().splitlines()[-1]
                    assert (
                        json.loads(last)["error"]["code"] == "deadline_exceeded"
                    )
                    continue
                assert status in (200, 504)
                if status != 200:
                    assert _error_code(body) == "deadline_exceeded"
            deadline = time.monotonic() + WATCHDOG_S
            while server.service.admission.in_flight:
                assert time.monotonic() < deadline, "worker slot leaked"
                time.sleep(0.01)
            status, _h, body = _scan(server, {"paths": "a.parquet"})
            assert status == 200 and body == want

    @pytest.mark.slow
    def test_spiked_hammer(self, corpus):
        """8 concurrent clients against a spiking source with mixed
        deadlines: every response is byte-identical or a typed timeout."""
        spiky = lambda p: FlakySource.latency_spike(  # noqa: E731
            LocalFileSource(p), seed=29, p=0.2, ms=15.0
        )
        with ScanServer(
            ServeConfig(port=0, root=str(corpus), cache_mb=0, source_factory=spiky)
        ) as server:
            server.start_background()
            want = _expected_jsonl(corpus, ["a.parquet", "b.parquet"])
            errors: list = []

            def hammer(i):
                try:
                    for k in range(3):
                        hdrs = (
                            {"X-Timeout-Ms": "60"} if (i + k) % 3 == 0 else {}
                        )
                        try:
                            status, _h, body = _scan(
                                server, {"paths": "*.parquet"}, hdrs
                            )
                        except http.client.IncompleteRead as e:
                            last = e.partial.decode().splitlines()[-1]
                            code = json.loads(last)["error"]["code"]
                            assert code == "deadline_exceeded"
                            continue
                        if status == 200:
                            assert body == want
                        else:
                            assert status == 504
                            assert _error_code(body) == "deadline_exceeded"
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [
                threading.Thread(target=hammer, args=(i,)) for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(WATCHDOG_S * 2)
                assert not t.is_alive(), "hammer thread hung"
            assert not errors


# -- the CLI face --------------------------------------------------------------


class TestServeCLI:
    def test_serve_daemon_subprocess_sigterm(self, corpus):
        import subprocess
        import sys
        import urllib.request

        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "parquet_tpu.tools.parquet_tool",
                "serve",
                "--port",
                "0",
                "--root",
                str(corpus),
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        try:
            line = proc.stdout.readline()
            assert line.startswith("serve: listening on http://"), line
            url = line.split()[-1]
            body = json.dumps({"paths": "a.parquet", "limit": 2}).encode()
            req = urllib.request.Request(url + "/v1/scan", data=body, method="POST")
            got = urllib.request.urlopen(req, timeout=WATCHDOG_S).read()
            assert got.count(b"\n") == 2
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=WATCHDOG_S)
            assert proc.returncode == 0
            assert "drained, bye" in out
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate(timeout=5)

    def test_cli_import_stays_serve_lazy(self):
        import subprocess
        import sys

        # `parquet-tool cat/head/meta` must not pay the serve-package
        # (http.server, pools) import — only `serve`/`scan --filters` do
        code = (
            "import sys; import parquet_tpu.tools.parquet_tool; "
            "assert 'parquet_tpu.serve' not in sys.modules, 'serve imported eagerly'; "
            "assert 'http.server' not in sys.modules, 'http.server imported eagerly'"
        )
        subprocess.run(
            [sys.executable, "-c", code], check=True, timeout=WATCHDOG_S * 2
        )

    def test_scan_filters_json_shares_the_spec_parser(self, corpus, capsys):
        from parquet_tpu.tools.parquet_tool import main as tool_main

        rc = tool_main(
            [
                "scan",
                str(corpus / "a.parquet"),
                "--columns",
                "id",
                "--filters",
                json.dumps([["id", "<", ROW_GROUP]]),
                "--json",
            ]
        )
        out = capsys.readouterr().out
        assert rc == 0
        doc = json.loads(out.strip().splitlines()[-1])
        assert doc["pruning"]["units_admitted"] == 1
        assert doc["pruning"]["units_pruned_stats"] == (
            doc["pruning"]["units_total"] - 1
        )
        assert doc["rows"] == ROW_GROUP
        # a bad spec fails with the shared parser's message, not a traceback
        rc = tool_main(
            [
                "scan",
                str(corpus / "a.parquet"),
                "--filters",
                json.dumps([["id", "~", 1]]),
            ]
        )
        err = capsys.readouterr().err
        assert rc == 1 and "unknown filter op" in err
        # --filter and --filters are mutually exclusive
        rc = tool_main(
            [
                "scan",
                str(corpus / "a.parquet"),
                "--filter",
                "id < 5",
                "--filters",
                "[]",
            ]
        )
        err = capsys.readouterr().err
        assert rc == 1 and "not both" in err


class TestRemoteMap:
    """PR 17: `--remote-map prefix=URL` — requested paths under a mapped
    prefix resolve to object-store URLs and scan through the ordinary
    remote read path, while everything else keeps the root confinement
    (escapes through a mapping still die with the typed 403)."""

    def test_mapped_prefix_plans_and_scans_from_the_stub(self, corpus):
        from parquet_tpu.testing.httpstub import RangeHttpStub

        data = (corpus / "a.parquet").read_bytes()
        with RangeHttpStub(files={"a.parquet": data}) as stub:
            with ScanServer(
                ServeConfig(
                    port=0,
                    root=str(corpus),
                    remote_map={"warm": stub.base_url},
                )
            ) as server:
                server.start_background()
                status, _, body = _request(
                    server,
                    "POST",
                    "/v1/plan",
                    {"paths": "warm/a.parquet"},
                )
                assert status == 200, body
                assert json.loads(body)["rows"] == ROWS_A
                status, _, body = _scan(
                    server, {"paths": "warm/a.parquet", "columns": ["id"]}
                )
                assert status == 200, body
                assert body == _expected_jsonl(
                    corpus, ["a.parquet"], columns=["id"]
                )
                assert stub.requests > 0  # the bytes really came remotely
                # local paths still work side by side with the mapping
                status, _, _ = _scan(server, {"paths": "b.parquet", "limit": 1})
                assert status == 200

    def test_escape_through_a_mapping_is_typed_403(self, corpus):
        from parquet_tpu.testing.httpstub import RangeHttpStub

        with RangeHttpStub(files={"x": b"irrelevant"}) as stub:
            with ScanServer(
                ServeConfig(
                    port=0,
                    root=str(corpus),
                    remote_map={"warm": stub.base_url},
                )
            ) as server:
                server.start_background()
                status, _, body = _scan(
                    server, {"paths": "warm/../../../etc/passwd"}
                )
                assert status == 403
                assert _error_code(body) == "path_outside_root"

    def test_cli_rejects_malformed_remote_map_spec(self, capsys):
        from parquet_tpu.tools.parquet_tool import main as tool_main

        rc = tool_main(
            ["serve", "--port", "0", "--remote-map", "no-equals-here"]
        )
        assert rc == 2
        assert "remote-map" in capsys.readouterr().err
        rc = tool_main(
            ["serve", "--port", "0", "--remote-map", "p=ftp://nope"]
        )
        assert rc == 2


class TestRequestHygiene:
    """Connection-level contracts: bounded body buffering, keep-alive
    integrity after typed errors, and config validation at startup."""

    def test_oversized_body_413_before_buffering(self, corpus):
        # the DECLARED Content-Length is rejected before a byte is
        # buffered — a client cannot make the daemon hold its body in RAM
        with ScanServer(
            ServeConfig(port=0, root=str(corpus), max_body_bytes=64)
        ) as server:
            server.start_background()
            big = {"paths": "a.parquet", "columns": ["id", "v", "name"] * 20}
            status, _h, body = _scan(server, big)
            assert status == 413 and _error_code(body) == "body_too_large"
            # the daemon stays healthy for right-sized requests
            assert _scan(server, {"paths": "a.parquet", "limit": 1})[0] == 200

    def test_keepalive_survives_error_with_unread_body(self, server):
        # a typed error sent BEFORE the route read the POST body must not
        # leave body bytes behind for the next request on the connection
        conn = http.client.HTTPConnection(
            server.host, server.port, timeout=WATCHDOG_S
        )
        try:
            payload = json.dumps({"paths": "a.parquet"}).encode()
            conn.request("POST", "/v1/nope", body=payload)
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 404 and _error_code(body) == "no_such_route"
            # SAME connection: the next request must parse cleanly, not be
            # read out of leftover body bytes (stdlib HTML 400)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["status"] == "ok"
        finally:
            conn.close()

    def test_bad_timeouts_rejected_at_startup(self):
        with pytest.raises(ValueError, match="default_timeout_s"):
            ServeConfig(default_timeout_s=-1)
        with pytest.raises(ValueError, match="max_timeout_s"):
            ServeConfig(max_timeout_s=0)
        with pytest.raises(ValueError, match="max_body_bytes"):
            ServeConfig(max_body_bytes=0)

    def test_cli_rejects_negative_timeout(self, corpus, capsys):
        # a user guessing -1 means "no timeout" (0 is the documented
        # disable) must fail at startup, not run a daemon that 504s
        # every request instantly
        from parquet_tpu.tools.parquet_tool import main as tool_main

        rc = tool_main(
            ["serve", "--port", "0", "--root", str(corpus), "--timeout-s", "-1"]
        )
        err = capsys.readouterr().err
        assert rc == 1 and "default_timeout_s" in err


# -- flight recorder + request correlation (parquet_tpu.obs over serve) --------


class TestFlightRecorder:
    """The PR-9 operator story: a client-supplied X-Request-Id is echoed,
    sanitized, and retrievable at /v1/debug/requests/<id> with status,
    tenant, pruning summary, queue-wait and stage rollup — plus a
    Perfetto-loadable trace when sampled/slow/errored. The ring and its
    trace retention stay bounded under a concurrent hammer."""

    @pytest.fixture()
    def sampled_server(self, corpus):
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus), cache_mb=32, trace_sample_rate=1.0
            )
        ) as s:
            s.start_background()
            yield s

    def test_request_id_roundtrip_record_and_trace(self, sampled_server):
        server = sampled_server
        body_spec = {"paths": "a.parquet", "columns": ["id"]}
        status, headers, body = _scan(
            server, body_spec, headers={"X-Request-Id": "demo"}
        )
        assert status == 200
        assert headers.get("X-Request-Id") == "demo"  # echoed verbatim
        # byte identity: the correlation header must not perturb the payload
        status2, headers2, body2 = _scan(server, body_spec)
        assert status2 == 200 and body2 == body
        assert headers2.get("X-Request-Id")  # generated when not supplied

        s, doc = _settled_record(server, "demo")
        assert s == 200
        assert doc["id"] == "demo"
        assert doc["endpoint"] == "/v1/scan"
        assert doc["tenant"] == "default"
        assert doc["status"] == 200
        assert doc["open"] is False
        assert doc["bytes"] == len(body)  # payload bytes, chunked framing off
        assert doc["duration_ms"] > 0
        assert doc["queue_wait_ms"] >= 0
        plan = doc["plan"]
        assert plan["files"] == 1 and plan["units_admitted"] >= 1
        assert "units_pruned_stats" in plan and "units_pruned_bloom" in plan
        stages = doc["stages"]
        assert stages and all(
            # nested_seconds rides sub-clocked stages only (the share of
            # a stage's time already billed to an enclosing stage)
            {"seconds", "bytes", "calls"}
            <= set(v)
            <= {"seconds", "bytes", "calls", "nested_seconds"}
            for v in stages.values()
        )
        assert "pool.wait" in stages  # the queue-wait rollup's source

        # rate 1.0: the span tree was kept, and it is Perfetto-shaped
        assert doc["has_trace"] and doc["trace_kind"] == "sampled"
        s, _h, b = _request(server, "GET", "/v1/debug/requests/demo/trace")
        assert s == 200
        tr = json.loads(b)
        assert tr["traceEvents"]
        for ev in tr["traceEvents"]:
            assert "ph" in ev and "name" in ev and "pid" in ev
        req_meta = dict(tr["otherData"]["request"])
        # the cross-process join key rides the debug trace (trace-merge
        # stitches per-process dumps on it); 32-hex, never the raw header
        tid = req_meta.pop("trace_id")
        assert isinstance(tid, str) and len(tid) == 32
        assert req_meta == {
            "id": "demo", "endpoint": "/v1/scan", "tenant": "default",
        }

        # the listing includes it, newest first
        s, _h, b = _request(server, "GET", "/v1/debug/requests")
        reqs = json.loads(b)["requests"]
        assert any(r["id"] == "demo" for r in reqs)

    def test_query_record_holds_every_request_phase(self, sampled_server):
        """PR 37: a request's own phases are stages of its trace, so the
        flight record's rollup (and the trace served beside it) accounts for
        the handler thread from the first body byte to the last answer byte:
        serve.parse, serve.admit (the gate; the byte charge after the plan
        is a span of the same name), serve.plan — a stage now, so it has
        seconds — and serve.respond once a request, serve.open_reader once a
        unit, nested in the unit's serve.aggregate."""
        server = sampled_server
        body = {
            "paths": ["a.parquet", "b.parquet"],
            "filters": [["id", ">=", 100]],
            "aggregates": ["count", ["sum", "id"]],
        }
        status, _h, payload = _request(
            server, "POST", "/v1/query", body, headers={"X-Request-Id": "phases"}
        )
        assert status == 200, payload
        units = json.loads(payload)["units"]
        assert units == -(-ROWS_A // ROW_GROUP) + -(-ROWS_B // ROW_GROUP)
        s, doc = _settled_record(server, "phases")
        assert s == 200
        stages = doc["stages"]
        for name in ("serve.parse", "serve.admit", "serve.plan", "serve.respond"):
            assert stages[name]["calls"] == 1, (name, stages.get(name))
            assert stages[name]["seconds"] > 0, name
            assert "nested_seconds" not in stages[name], name  # the request's own wall
        assert stages["serve.open_reader"]["calls"] == units
        assert stages["serve.aggregate"]["calls"] == units
        assert stages["serve.open_reader"]["nested_seconds"] == pytest.approx(
            stages["serve.open_reader"]["seconds"]
        )
        assert stages["serve.merge"]["calls"] == units

        s, _h, b = _request(server, "GET", "/v1/debug/requests/phases/trace")
        assert s == 200
        events = [e for e in json.loads(b)["traceEvents"] if e["ph"] == "X"]
        by_name: dict = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        # the two halves of admission and of a unit's reader are both in the trace
        assert len(by_name["serve.admit"]) == 2
        assert len(by_name["serve.open_reader"]) == 2 * units
        assert by_name["serve.plan"][0]["args"]["paths"] == "a.parquet,b.parquet"
        # in time order on the handler's lane, and every span names its parent
        (lane,) = {e["tid"] for n in ("serve.parse", "serve.plan", "serve.respond") for e in by_name[n]}
        order = sorted(
            (e for e in events if e["tid"] == lane and e["name"].startswith("serve.")
             and e["name"] != "serve.merge"),
            key=lambda e: e["ts"],
        )
        assert [e["name"] for e in order] == [
            "serve.parse", "serve.admit", "serve.plan", "serve.admit", "serve.respond",
        ]
        ids = {e["args"]["id"] for e in events}
        assert len(ids) == len(events)
        assert all(e["args"].get("parent", 0) in ids for e in events)
        aggregates = {e["args"]["id"] for e in by_name["serve.aggregate"]}
        assert {e["args"]["parent"] for e in by_name["serve.open_reader"]} == aggregates

    def test_scan_record_holds_parse_admit_plan_and_streams_its_answer(
        self, sampled_server
    ):
        """A scan's answer is the chunked write serve.stream brackets: it
        has the head's three phases and no serve.respond."""
        server = sampled_server
        status, _h, _b = _scan(
            server,
            {"paths": "a.parquet", "columns": ["id"]},
            headers={"X-Request-Id": "scan-phases"},
        )
        assert status == 200
        s, doc = _settled_record(server, "scan-phases")
        assert s == 200
        stages = doc["stages"]
        for name in ("serve.parse", "serve.admit", "serve.plan"):
            assert stages[name]["calls"] == 1 and stages[name]["seconds"] > 0, name
        units = doc["plan"]["units_admitted"]
        assert stages["serve.open_reader"]["calls"] == units == stages["serve.execute"]["calls"]
        assert stages["serve.stream"]["calls"] >= 1
        assert "serve.respond" not in stages

    def test_hostile_request_id_sanitized_everywhere(self, sampled_server):
        server = sampled_server
        raw = "e{vil}|id;" + "x" * 200
        status, headers, _b = _scan(
            server,
            {"paths": "a.parquet", "columns": ["id"]},
            headers={"X-Request-Id": raw},
        )
        assert status == 200
        rid = headers["X-Request-Id"]
        assert len(rid) <= 64
        assert all(c.isalnum() or c in "._:-" for c in rid)
        s, _h, b = _request(server, "GET", f"/v1/debug/requests/{rid}")
        assert s == 200 and json.loads(b)["id"] == rid

    def test_errored_request_always_keeps_trace(self, corpus):
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus), cache_mb=32, trace_sample_rate=0.0
            )
        ) as server:
            server.start_background()
            status, _h, body = _scan(
                server,
                {"paths": "missing.parquet"},
                headers={"X-Request-Id": "whoops"},
            )
            assert status == 404
            assert json.loads(body)["error"]["request_id"] == "whoops"
            s, doc = _settled_record(server, "whoops")
            assert doc["status"] == 404
            assert doc["error"]  # the truncated message, retrievable later
            assert doc["has_trace"] and doc["trace_kind"] == "error"
            s, _h, _b = _request(
                server, "GET", "/v1/debug/requests/whoops/trace"
            )
            assert s == 200

    def test_slow_request_counts_and_keeps_trace(self, corpus):
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus), cache_mb=32,
                trace_sample_rate=0.0, slow_ms=0.0001,
            )
        ) as server:
            server.start_background()
            snap = metrics.snapshot()
            status, _h, _b = _scan(
                server,
                {"paths": "a.parquet", "columns": ["id"]},
                headers={"X-Request-Id": "tortoise"},
            )
            assert status == 200
            d = _settled_delta(
                snap, 'serve_slow_requests_total{endpoint="/v1/scan"}'
            )
            assert d.get('serve_slow_requests_total{endpoint="/v1/scan"}', 0) >= 1
            s, doc = _settled_record(server, "tortoise")
            assert doc["trace_kind"] == "slow" and doc["has_trace"]

    def test_unsampled_fast_request_has_no_trace(self, corpus):
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus), cache_mb=32, trace_sample_rate=0.0
            )
        ) as server:
            server.start_background()
            status, _h, _b = _scan(
                server,
                {"paths": "a.parquet", "columns": ["id"]},
                headers={"X-Request-Id": "quick"},
            )
            assert status == 200
            s, _h, b = _request(server, "GET", "/v1/debug/requests/quick")
            assert s == 200 and json.loads(b)["has_trace"] is False
            s, _h, b = _request(
                server, "GET", "/v1/debug/requests/quick/trace"
            )
            assert s == 404 and _error_code(b) == "no_trace"

    def test_unknown_id_and_bad_limit_are_typed(self, server):
        s, _h, b = _request(server, "GET", "/v1/debug/requests/never-seen")
        assert s == 404 and _error_code(b) == "no_such_request"
        s, _h, b = _request(server, "GET", "/v1/debug/requests?limit=banana")
        assert s == 400 and _error_code(b) == "bad_request"
        s, _h, b = _request(server, "GET", "/v1/debug/requests?limit=0")
        assert s == 400 and _error_code(b) == "bad_request"
        s, _h, b = _request(server, "GET", "/v1/debug/requests/a/b/c")
        assert s == 404 and _error_code(b) == "no_such_route"

    def test_plan_requests_are_recorded_per_endpoint(self, sampled_server):
        server = sampled_server
        snap = metrics.snapshot()
        s, h, _b = _request(
            server, "POST", "/v1/plan", {"paths": "a.parquet"},
            {"X-Request-Id": "dry-run"},
        )
        assert s == 200 and h.get("X-Request-Id") == "dry-run"
        s, doc = _settled_record(server, "dry-run")
        assert doc["endpoint"] == "/v1/plan" and doc["plan"]["files"] == 1
        d = _settled_delta(
            snap, 'serve_request_seconds_count{endpoint="/v1/plan"}'
        )
        assert d.get('serve_request_seconds_count{endpoint="/v1/plan"}', 0) >= 1

    def test_ring_stays_bounded_under_http_requests(self, corpus):
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus), cache_mb=32, debug_ring_size=8,
                trace_sample_rate=1.0,
            )
        ) as server:
            server.start_background()
            for i in range(20):
                s, _h, _b = _request(
                    server, "GET", "/v1/plan?paths=a.parquet",
                    headers={"X-Request-Id": f"r{i:02d}"},
                )
                assert s == 200
            # the PROCESS-wide recorder may hold library one-shots from
            # other tests in this run; the REQUEST ring is what this
            # daemon's 20 plans hammer, and both rings share the bound
            stats = server.service.recorder.stats()
            assert stats["requests"] <= 8
            assert stats["library"] <= 8
            assert stats["indexed"] <= stats["records"]
            s, _h, b = _request(
                server, "GET", "/v1/debug/requests?limit=1000&endpoint=/v1/plan"
            )
            reqs = json.loads(b)["requests"]
            assert len(reqs) <= 8
            assert reqs[0]["id"] == "r19"  # newest first
            # evicted ids 404, retained ones resolve
            s, _h, _b = _request(server, "GET", "/v1/debug/requests/r00")
            assert s == 404
            s, _h, _b = _request(server, "GET", "/v1/debug/requests/r19")
            assert s == 200

    def test_eviction_under_hammer_bounds_memory(self):
        """8 writer threads churn a tiny ring (every record slow+traced, the
        worst case for trace retention) while readers list/get — occupancy
        never exceeds the configured bounds."""
        from parquet_tpu.obs.recorder import FlightRecorder, ObsConfig
        from parquet_tpu.utils.trace import decode_trace

        rec = FlightRecorder(
            ObsConfig(ring_size=16, trace_sample_rate=1.0, slow_ms=0.001,
                      max_traces=4)
        )
        stop = threading.Event()
        violations = []

        def writer(k):
            for i in range(200):
                with decode_trace() as tr:
                    pass
                r = rec.begin("/v1/scan", f"t{k}", request_id=f"w{k}-{i}")
                rec.finish(r, 200, nbytes=64, trace=tr, duration_s=0.01)

        def reader():
            while not stop.is_set():
                rec.list(limit=50)
                rec.get("w0-5")
                st = rec.stats()
                if st["records"] > 16 or st["traces"] > 4:
                    violations.append(st)

        threads = [
            threading.Thread(target=writer, args=(k,)) for k in range(8)
        ] + [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads[:8]:
            t.join(WATCHDOG_S)
        stop.set()
        for t in threads[8:]:
            t.join(WATCHDOG_S)
        assert not any(t.is_alive() for t in threads)
        assert not violations, violations[:3]
        st = rec.stats()
        assert st["records"] <= 16 and st["indexed"] <= 16
        assert st["traces"] <= 4
        # every retained record is one of the newest; the ring dropped
        # ~1584 records without the index leaking any of them
        assert len(rec.list(limit=100)) <= 16


class TestDebugCli:
    """`parquet-tool debug <url>` — the operator client for the daemon's
    flight recorder (list / one record / Perfetto trace export)."""

    @pytest.fixture()
    def live(self, corpus):
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus), cache_mb=32, trace_sample_rate=1.0
            )
        ) as s:
            s.start_background()
            _scan(
                s, {"paths": "a.parquet", "columns": ["id"]},
                headers={"X-Request-Id": "cli-demo"},
            )
            yield s, f"http://{s.host}:{s.port}"

    def test_list_table(self, live, capsys):
        from parquet_tpu.tools.parquet_tool import main as tool_main

        _server, url = live
        assert tool_main(["debug", url]) == 0
        out = capsys.readouterr().out
        assert "ID" in out and "ENDPOINT" in out and "WAIT_MS" in out
        assert "cli-demo" in out and "/v1/scan" in out and "sampled" in out

    def test_one_record_json(self, live, capsys):
        from parquet_tpu.tools.parquet_tool import main as tool_main

        _server, url = live
        assert tool_main(["debug", url, "--id", "cli-demo"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["id"] == "cli-demo" and doc["status"] == 200
        assert doc["plan"]["files"] == 1 and doc["stages"]

    def test_trace_export_is_perfetto_loadable(self, live, tmp_path, capsys):
        from parquet_tpu.tools.parquet_tool import main as tool_main

        _server, url = live
        out_path = tmp_path / "trace.json"
        assert tool_main(
            ["debug", url, "--id", "cli-demo", "--trace", "-o", str(out_path)]
        ) == 0
        assert "trace events" in capsys.readouterr().out
        doc = json.loads(out_path.read_text())
        assert doc["traceEvents"]
        assert doc["otherData"]["request"]["id"] == "cli-demo"

    def test_slow_filter_and_scheme_default(self, live, capsys):
        from parquet_tpu.tools.parquet_tool import main as tool_main

        server, _url = live
        # bare host:port grows the http:// scheme; nothing is slow yet
        assert tool_main(
            ["debug", f"{server.host}:{server.port}", "--slow"]
        ) == 0
        assert "no recorded requests" in capsys.readouterr().out

    def test_unknown_id_is_typed_failure(self, live, capsys):
        from parquet_tpu.tools.parquet_tool import main as tool_main

        _server, url = live
        assert tool_main(["debug", url, "--id", "nope"]) == 1
        assert "no_such_request" in capsys.readouterr().err

    def test_trace_without_id_rejected(self, live, capsys):
        from parquet_tpu.tools.parquet_tool import main as tool_main

        _server, url = live
        assert tool_main(["debug", url, "--trace"]) == 1
        assert "--trace requires --id" in capsys.readouterr().err


class TestTraceEviction:
    def test_evicted_trace_404_names_the_right_knob(self, corpus):
        """A record that QUALIFIED for a trace but lost it to max_traces
        pressure must say so — not claim it was never sampled."""
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus), cache_mb=32, trace_sample_rate=0.0
            )
        ) as server:
            server.start_background()
            # every error keeps a trace; default max_traces=16, so the
            # 17th evicts the first record's tree (the record stays)
            for i in range(17):
                _scan(
                    server, {"paths": "missing.parquet"},
                    headers={"X-Request-Id": f"e{i:02d}"},
                )
            # settle the LAST qualifier first: its finish step (which
            # attaches the tree and evicts e00's) runs post-flush
            _settled_record(server, "e16")
            s, doc = _settled_record(server, "e00")
            assert s == 200
            assert doc["trace_kind"] == "error" and doc["has_trace"] is False
            s, _h, b = _request(server, "GET", "/v1/debug/requests/e00/trace")
            assert s == 404 and _error_code(b) == "trace_evicted"
            assert "--debug-max-traces" in json.loads(b)["error"]["message"]
            # the newest qualifier still serves its tree
            s, _h, _b = _request(
                server, "GET", "/v1/debug/requests/e16/trace"
            )
            assert s == 200


class TestObsKnobOwnership:
    def test_serve_defaults_mirror_obsconfig(self):
        """ObsConfig owns the observability numbers; ServeConfig must not
        restate them (restated copies drift silently)."""
        from parquet_tpu.obs.recorder import ObsConfig

        cfg, obs = ServeConfig(), ObsConfig()
        assert cfg.trace_sample_rate == obs.trace_sample_rate
        assert cfg.slow_ms == obs.slow_ms
        assert cfg.debug_ring_size == obs.ring_size
        assert cfg.debug_max_traces == obs.max_traces

    def test_admission_rejections_rate_limit_per_code(self):
        """A queue_full flood must not absorb the log line for a DIFFERENT
        rejection code — the limiter keys on admission_rejected:<code>."""
        adm = AdmissionController(max_inflight=1)
        before = metrics.get(
            "log_events_total", event="admission_rejected:queue_full"
        ) + metrics.get(
            "log_suppressed_total", event="admission_rejected:queue_full"
        )
        with adm.admit("t0"), pytest.raises(ServeError, match="max in-flight"):
            adm.admit("t")
        after = metrics.get(
            "log_events_total", event="admission_rejected:queue_full"
        ) + metrics.get(
            "log_suppressed_total", event="admission_rejected:queue_full"
        )
        assert after == before + 1  # keyed per code, counted either way
