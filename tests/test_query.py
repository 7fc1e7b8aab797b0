"""Aggregation push-down (/v1/query + `scan --aggregate`): the contracts.

Pinned here:
  * protocol: malformed aggregate specs fail with typed 400 bodies before
    any file is touched;
  * semantics: per-unit partials merged across units equal ONE whole-corpus
    pyarrow aggregation — null skipping, NaN propagation, decimal types,
    grouped and global (the differential oracle the merge rules are pinned
    against);
  * bytes: the daemon's /v1/query response, run_local_query, and
    `parquet-tool scan --aggregate` render IDENTICAL bytes;
  * bounded cardinality: group-by overflow is a typed 413, not memory
    growth;
  * admission parity with /v1/scan: the tenant byte budget charges the
    SAME plan estimate (aggregation is not a budget bypass), and
    deadline / brownout / drain produce the same typed rejections on the
    new endpoint;
  * observability: the flight record carries mask selectivity next to the
    pruning summary, and serve_aggregate_requests_total moves.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from parquet_tpu.io.source import LocalFileSource
from parquet_tpu.serve import (
    QueryRequest,
    ScanServer,
    ServeConfig,
    ServeError,
    parse_query_request,
    render_query_body,
    run_local_query,
)
from parquet_tpu.serve.protocol import DEFAULT_MAX_GROUPS
from parquet_tpu.utils import metrics

WATCHDOG_S = 30.0

ROWS_PER_FILE = 1500
GROUP = 400


def _write_corpus(d):
    rng = np.random.default_rng(41)
    base = 0
    for name in ("a.parquet", "b.parquet"):
        n = ROWS_PER_FILE
        v = rng.standard_normal(n)
        v[::17] = np.nan
        t = pa.table(
            {
                "id": pa.array(np.arange(base, base + n, dtype=np.int64)),
                "v": pa.array(
                    [None if i % 11 == 0 else float(x) for i, x in enumerate(v)],
                    pa.float64(),
                ),
                "name": pa.array([f"g{i % 7}" for i in range(n)]),
                "amount": pa.array(
                    [None if i % 13 == 0 else __import__("decimal").Decimal(i) / 4
                     for i in range(n)],
                    pa.decimal128(12, 2),
                ),
            }
        )
        pq.write_table(t, str(d / name), row_group_size=GROUP)
        base += n
    return d


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    return _write_corpus(tmp_path_factory.mktemp("query_corpus"))


@pytest.fixture()
def server(corpus):
    with ScanServer(ServeConfig(port=0, root=str(corpus))) as srv:
        yield srv.start_background()


def _post(server, route, body, headers=None, timeout=WATCHDOG_S):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=timeout)
    try:
        conn.request("POST", route, body=json.dumps(body).encode(),
                     headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _get(server, route):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=WATCHDOG_S)
    try:
        conn.request("GET", route)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _query(paths, **kw) -> QueryRequest:
    body = {"paths": paths, "aggregates": kw.pop("aggregates", ["count"]), **kw}
    return parse_query_request(json.dumps(body).encode())


def _whole_table(corpus, filters=None):
    t = pa.concat_tables(
        [pq.read_table(str(corpus / n)) for n in ("a.parquet", "b.parquet")]
    )
    if filters is not None:
        col, op, val = filters[0]
        t = t.filter({
            ">": pc.greater, ">=": pc.greater_equal, "<": pc.less,
        }[op](t.column(col), val))
    return t


# -- protocol ------------------------------------------------------------------


class TestSpec:
    @pytest.mark.parametrize(
        "body",
        [
            {"aggregates": ["count"]},  # no paths
            {"paths": "x.parquet"},  # no aggregates
            {"paths": "x.parquet", "aggregates": []},
            {"paths": "x.parquet", "aggregates": ["median"]},
            {"paths": "x.parquet", "aggregates": [["sum"]]},  # sum needs a column
            {"paths": "x.parquet", "aggregates": [{"op": "sum", "col": "v"}]},
            {"paths": "x.parquet", "aggregates": ["count"], "group_by": [1]},
            {"paths": "x.parquet", "aggregates": ["count"], "max_groups": 0},
            {"paths": "x.parquet", "aggregates": ["count"], "limit": 3},
        ],
    )
    def test_rejections_are_typed(self, body):
        with pytest.raises(ServeError) as ei:
            parse_query_request(json.dumps(body).encode())
        assert ei.value.status == 400

    def test_accepts_full_request(self):
        q = parse_query_request(json.dumps({
            "paths": ["a.parquet"],
            "filters": [["v", ">", 0]],
            "aggregates": ["count", ["sum", "v"], {"op": "min", "column": "id"}],
            "group_by": "name",
            "max_groups": 5,
            "shard": "0/2",
            "timeout_ms": 1000,
        }).encode())
        assert q.aggregates[0].op == "count" and q.aggregates[0].column is None
        assert q.aggregates[1] == ("sum", "v", None)
        assert q.group_by == ("name",) and q.max_groups == 5
        assert q.shard == (0, 2) and q.timeout_ms == 1000

    def test_endpoint_bad_spec_is_typed_400(self, server):
        status, _h, body = _post(
            server, "/v1/query", {"paths": "a.parquet", "aggregates": ["median"]}
        )
        assert status == 400
        assert json.loads(body)["error"]["code"] == "bad_aggregates"


# -- semantics vs the pyarrow oracle -------------------------------------------


class TestSemantics:
    def test_global_matches_pyarrow(self, corpus):
        q = _query(
            [str(corpus / "*.parquet")],
            aggregates=["count", ["count", "v"], ["sum", "v"], ["min", "v"],
                        ["max", "v"], ["sum", "amount"], ["min", "amount"]],
            filters=[["id", ">=", 100]],
        )
        got = run_local_query(q.paths, q)["result"]
        t = _whole_table(corpus, [("id", ">=", 100)])
        assert got["count"] == t.num_rows
        assert got["count(v)"] == pc.count(t.column("v")).as_py()
        # NaN propagates through sum exactly as one whole-corpus kernel
        assert np.isnan(got["sum(v)"]) == np.isnan(pc.sum(t.column("v")).as_py())
        if not np.isnan(got["sum(v)"]):
            assert abs(got["sum(v)"] - pc.sum(t.column("v")).as_py()) < 1e-9
        assert got["min(v)"] == pc.min(t.column("v")).as_py()
        assert got["max(v)"] == pc.max(t.column("v")).as_py()
        assert got["sum(amount)"] == pc.sum(t.column("amount")).as_py()
        assert got["min(amount)"] == pc.min(t.column("amount")).as_py()

    def test_group_by_matches_pyarrow(self, corpus):
        q = _query(
            [str(corpus / "*.parquet")],
            aggregates=["count", ["sum", "v"], ["min", "id"], ["max", "id"]],
            group_by=["name"],
            filters=[["v", ">", 0.0]],
        )
        got = run_local_query(q.paths, q)
        t = _whole_table(corpus, [("v", ">", 0.0)])
        ora = t.group_by(["name"]).aggregate(
            [([], "count_all"), ("v", "sum"), ("id", "min"), ("id", "max")]
        )
        assert got["group_count"] == ora.num_rows
        om = {r["key"][0]: r["aggregates"] for r in got["groups"]}
        for i in range(ora.num_rows):
            k = ora.column("name")[i].as_py()
            assert om[k]["count"] == ora.column("count_all")[i].as_py()
            assert abs(om[k]["sum(v)"] - ora.column("v_sum")[i].as_py()) < 1e-9
            assert om[k]["min(id)"] == ora.column("id_min")[i].as_py()
            assert om[k]["max(id)"] == ora.column("id_max")[i].as_py()
        # deterministic ordering: groups sort by canonical key encoding
        keys = [r["key"] for r in got["groups"]]
        assert keys == sorted(keys)

    def test_all_null_aggregates_are_null(self, tmp_path):
        p = tmp_path / "nulls.parquet"
        pq.write_table(
            pa.table({"x": pa.array([None, None], pa.int64())}), str(p)
        )
        q = _query([str(p)], aggregates=[["sum", "x"], ["min", "x"], ["count", "x"]])
        got = run_local_query(q.paths, q)["result"]
        assert got["sum(x)"] is None and got["min(x)"] is None
        assert got["count(x)"] == 0

    def test_count_star_without_filters_decodes_nothing(self, corpus):
        snap = metrics.snapshot()
        q = _query([str(corpus / "*.parquet")])
        got = run_local_query(q.paths, q)
        d = metrics.delta(snap)
        assert got["result"]["count"] == 2 * ROWS_PER_FILE
        assert got["rows_scanned"] == 2 * ROWS_PER_FILE
        # footers are read; data pages are NOT
        assert not d.get("pages_decoded_total", 0)

    def test_group_overflow_is_typed(self, corpus):
        q = _query(
            [str(corpus / "a.parquet")], aggregates=["count"],
            group_by=["name"], max_groups=3,
        )
        with pytest.raises(ServeError) as ei:
            run_local_query(q.paths, q)
        assert ei.value.status == 413 and ei.value.code == "group_overflow"

    def test_shard_partitions_units(self, corpus):
        q_full = _query([str(corpus / "*.parquet")])
        full = run_local_query(q_full.paths, q_full)
        parts = []
        for i in range(2):
            q = _query([str(corpus / "*.parquet")], shard=[i, 2])
            parts.append(run_local_query(q.paths, q))
        assert sum(p["result"]["count"] for p in parts) == full["result"]["count"]
        assert sum(p["units"] for p in parts) == full["units"]


# -- the endpoint --------------------------------------------------------------


class TestEndpoint:
    BODY = {
        "paths": "*.parquet",
        "filters": [["v", ">", 0.0]],
        "aggregates": ["count", ["sum", "v"], ["max", "id"]],
        "group_by": ["name"],
    }

    def test_daemon_bytes_match_local_twin(self, server, corpus):
        status, headers, payload = _post(server, "/v1/query", self.BODY)
        assert status == 200, payload
        assert headers.get("Content-Type") == "application/json"
        q = parse_query_request(
            json.dumps({**self.BODY, "paths": [str(corpus / "*.parquet")]}).encode()
        )
        assert payload == render_query_body(run_local_query(q.paths, q))

    def test_aggregate_metric_moves(self, server):
        snap = metrics.snapshot()
        assert _post(server, "/v1/query", self.BODY)[0] == 200
        d = metrics.delta(snap)
        assert d.get("serve_aggregate_requests_total", 0) >= 1

    def test_flight_record_carries_selectivity(self, server):
        rid = "q-selectivity-test"
        status, _h, _b = _post(
            server, "/v1/query", self.BODY, headers={"X-Request-Id": rid}
        )
        assert status == 200
        status, body = _get(server, f"/v1/debug/requests/{rid}")
        assert status == 200
        rec = json.loads(body)
        res = rec["plan"]["residual"]
        assert res["rows_scanned"] == 2 * ROWS_PER_FILE
        assert 0 < res["rows_matched"] < res["rows_scanned"]
        assert res["selectivity"] == round(
            res["rows_matched"] / res["rows_scanned"], 6
        )
        # the pruning summary is still there, NEXT to the residual stats
        assert "units_admitted" in rec["plan"]

    def test_budget_charges_plan_estimate(self, corpus):
        """Aggregation must not bypass the scanned-byte budget: /v1/query
        charges the same plan estimate /v1/scan would."""
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus),
                tenant_budget_mb=1, budget_window_s=3600.0,
            )
        ) as server:
            server.start_background()
            headers = {"X-Tenant": "alice"}
            status = None
            for _ in range(200):
                status, _h, body = _post(
                    server, "/v1/query", self.BODY, headers=headers
                )
                if status != 200:
                    break
            assert status == 429
            assert json.loads(body)["error"]["code"] == "tenant_over_budget"
            # budgets are per tenant
            s2, _h, _b = _post(
                server, "/v1/query", self.BODY, headers={"X-Tenant": "bob"}
            )
            assert s2 == 200

    def test_deadline_504_leaves_daemon_healthy(self, corpus):
        from parquet_tpu.testing.flaky import FlakySource

        slow = lambda p: FlakySource(  # noqa: E731
            LocalFileSource(p), seed=0, latency_s=0.25
        )
        with ScanServer(
            ServeConfig(
                port=0, root=str(corpus), cache_mb=0, source_factory=slow
            )
        ) as server:
            server.start_background()
            status, _h, body = _post(
                server, "/v1/query", self.BODY,
                headers={"X-Timeout-Ms": "120"},
            )
            assert status == 504
            assert json.loads(body)["error"]["code"] == "deadline_exceeded"
            assert _get(server, "/healthz")[0] == 200
            assert server.service.admission.in_flight == 0

    def test_drain_rejects_with_typed_503(self, server):
        server.service.admission.begin_drain()
        status, headers, body = _post(server, "/v1/query", self.BODY)
        assert status == 503
        assert json.loads(body)["error"]["code"] == "draining"

    def test_brownout_sheds_queries(self, corpus):
        with ScanServer(
            ServeConfig(port=0, root=str(corpus), brownout_depth=1)
        ) as server:
            server.start_background()
            # the first admission only SEEDS the brownout window's
            # baseline; the depth check applies from the second on
            assert _post(server, "/v1/query", self.BODY)[0] == 200
            metrics.set_gauge("pool_queue_depth", 5, pool="pqt-serve")
            try:
                status, headers, body = _post(server, "/v1/query", self.BODY)
                assert status == 503
                assert json.loads(body)["error"]["code"] == "brownout"
                assert "Retry-After" in headers
            finally:
                metrics.set_gauge("pool_queue_depth", 0, pool="pqt-serve")
            assert _post(server, "/v1/query", self.BODY)[0] == 200

    def test_concurrent_queries_identical(self, server, corpus):
        ref = _post(server, "/v1/query", self.BODY)[2]
        out: dict = {}

        def hammer(i):
            out[i] = _post(server, "/v1/query", self.BODY)

        threads = [threading.Thread(target=hammer, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WATCHDOG_S)
        assert all(not t.is_alive() for t in threads)
        for i, (status, _h, payload) in out.items():
            assert status == 200 and payload == ref, i

    def test_unreadable_file_is_typed_422(self, server, corpus, tmp_path):
        bad = corpus / "bad.parquet"
        bad.write_bytes(b"PAR1garbagegarbagePAR1")
        try:
            status, _h, body = _post(
                server, "/v1/query",
                {"paths": "bad.parquet", "aggregates": ["count", ["sum", "id"]],
                 "filters": [["id", ">", 0]]},
            )
            assert status == 422
            assert json.loads(body)["error"]["code"] == "unreadable_file"
        finally:
            bad.unlink()


# -- deadline plumbing (unit level, no HTTP) -----------------------------------


class TestExecutor:
    def test_expired_deadline_is_typed(self, corpus):
        from parquet_tpu.serve.admission import Deadline
        from parquet_tpu.serve.executor import execute_query
        from parquet_tpu.serve.protocol import ScanRequest
        from parquet_tpu.serve.session import ScanSession

        q = _query([str(corpus / "*.parquet")], aggregates=[["sum", "v"]])
        session = ScanSession()
        planned = session.plan(
            ScanRequest(
                paths=q.paths, columns=["v"], filters=None, limit=None,
                format="jsonl", shard=None, timeout_ms=None,
            )
        )
        t0 = time.monotonic()
        with pytest.raises(ServeError) as ei:
            execute_query(
                planned, q, session,
                deadline=Deadline(0.0, clock=time.monotonic),
            )
        assert ei.value.status == 504
        assert time.monotonic() - t0 < WATCHDOG_S

    def test_query_holds_a_pool_of_finished_partials_ahead(self, corpus, monkeypatch):
        """Results come back in plan order: a query may hold as many finished
        partials again as the pool has workers, so a worker that finished
        before the oldest unit is handed its next one. The answer is the same."""
        from parquet_tpu.serve import executor
        from parquet_tpu.serve.protocol import ScanRequest
        from parquet_tpu.serve.session import ScanSession

        q = _query([str(corpus / "*.parquet")], aggregates=[["sum", "id"], "count"])
        session = ScanSession()
        planned = session.plan(
            ScanRequest(
                paths=q.paths, columns=["id"], filters=None, limit=None,
                format="jsonl", shard=None, timeout_ms=None,
            )
        )
        assert len(planned.units) == 8
        seen = []
        real = executor._pipelined

        def spy(units, run_one, window, check, ahead=0):
            seen.append((window, ahead))
            return real(units, run_one, window, check, ahead)

        monkeypatch.setattr(executor, "_pipelined", spy)
        monkeypatch.setattr(executor, "pool_size", lambda: 3)
        body = executor.execute_query(planned, q, session)
        assert seen == [(3, 3)]
        assert body == run_local_query([str(corpus / "*.parquet")], q)

    @pytest.mark.parametrize("ahead,reached", [(0, 2), (2, 4)])
    def test_pipeline_refills_behind_a_slow_oldest_unit(self, ahead, reached):
        """While the oldest unit runs, `ahead` finished results may wait for
        it: the units behind it go on running, never more than `window` at
        once (nothing is parked in the pool's queue), and results still come
        back in plan order."""
        from parquet_tpu.serve.executor import _Check, _pipelined

        release, lock = threading.Event(), threading.Lock()
        started, running, most = [], [0], [0]

        def run_one(u):
            with lock:
                started.append(u)
                running[0] += 1
                most[0] = max(most[0], running[0])
            if u == 0:
                release.wait(WATCHDOG_S)
            with lock:
                running[0] -= 1
            return u

        gen = _pipelined(list(range(8)), run_one, 2, _Check(None), ahead)
        got = []
        t = threading.Thread(target=lambda: got.extend(gen))
        t.start()
        deadline = time.monotonic() + WATCHDOG_S
        while len(started) < reached and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)  # anything past the bound would have started by now
        with lock:
            assert sorted(started) == list(range(reached)) and got == []
        release.set()
        t.join(WATCHDOG_S)
        assert got == list(range(8)) and most[0] <= 2


# -- the merge: filed a unit, folded once ----------------------------------------

_D = __import__("decimal").Decimal
_DEC = pa.decimal128(38, 2)
MERGE_CASES = [
    ("count", [3, 0, 5, 7], None),
    ("sum", [_D("1.25"), None, _D("-7.50"), _D("99999999999999999999.99")], _DEC),
    ("sum", [None, None], _DEC),
    ("sum", [None, 4, None], pa.int64()),
    ("sum", [2**62, 2**62 - 1, -5], pa.int64()),
    ("min", [_D("3.00"), _D("-1.00"), None, _D("2.00")], _DEC),
    ("max", [7, None, 9, -3], pa.int64()),
    ("max", [1.5, float("nan"), None, 2.5], pa.float64()),
    ("avg", [(_D("10.00"), 4), None, (_D("0.50"), 1), (_D("-3.25"), 2)], _DEC),
    ("avg", [None, None, None], _DEC),
    ("avg", [(7, 2), (9, 3)], pa.int64()),
]


def _pairwise(op, vals, typ):
    """The fold a pair at a time, through the Arrow kernel each time."""
    acc = vals[0]
    for v in vals[1:]:
        if op == "count":
            acc = acc + v
        elif acc is None or v is None:
            acc = v if acc is None else acc
        elif op == "avg":
            acc = (pc.sum(pa.array([acc[0], v[0]], type=typ)).as_py(), acc[1] + v[1])
        else:
            acc = getattr(pc, op)(pa.array([acc, v], type=typ)).as_py()
    return acc


@pytest.mark.parametrize("op,vals,typ", MERGE_CASES, ids=[f"{c[0]}-{i}" for i, c in enumerate(MERGE_CASES)])
def test_one_fold_of_all_partials_is_the_pairwise_fold(op, vals, typ):
    from parquet_tpu.serve.aggregate import _merge_values

    got, want = _merge_values(op, list(vals), typ), _pairwise(op, list(vals), typ)
    assert repr(got) == repr(want)  # repr: nan equals nan, 1 is not 1.0


def test_absorb_files_partials_and_folds_them_once(monkeypatch):
    """absorb makes no Arrow call (each hands the GIL away, on the thread
    that feeds the unit pool); a key's partials fold when it holds _FOLD_AT
    of them and when the state is read, so the state stays bounded."""
    from parquet_tpu.serve import aggregate

    q = _query(["x.parquet"], aggregates=[["sum", "amount"], ["avg", "amount"], ["min", "id"], "count"],
               group_by=["name"])
    calls = []
    real = aggregate._merge_values
    monkeypatch.setattr(aggregate, "_merge_values", lambda op, vals, typ: calls.append((op, len(vals))) or real(op, vals, typ))
    state = aggregate.QueryState(q)
    types = [_DEC, _DEC, pa.int64(), None]
    units = aggregate._FOLD_AT + 10
    for u in range(units):
        groups = {("a",): [_D(u), (_D(u), 2), u, 3], ("b",): [None, None, -u, 1]}
        if u == 5:
            del groups[("b",)]  # a group absent from one unit
        state.absorb(((groups, types), 10, 4))
        assert all(len(held) < aggregate._FOLD_AT for cur in state._pending.values() for held in cur)
    # each key folded once so far, all its partials in one call an aggregate
    assert {n for _, n in calls} == {aggregate._FOLD_AT} and calls.count(("count", aggregate._FOLD_AT)) == 2
    total = sum(range(units))
    assert state.groups == {
        ("a",): [_D(total), (_D(total), 2 * units), 0, 3 * units],
        ("b",): [None, None, -(units - 1), units - 1],
    }
    assert (state.rows_scanned, state.rows_matched) == (10 * units, 4 * units)
    body = aggregate.result_dict(q, state, units=units)
    assert body["group_count"] == 2 and [g["key"] for g in body["groups"]] == [["a"], ["b"]]
    assert body["groups"][0]["aggregates"]["avg(amount)"] == aggregate.render_avg((_D(total), 2 * units), _DEC)
