"""Expression aggregates, DATE and DECIMAL through /v1/query: the wire form,
the host lane against the plain reference of TPC-H Q6 over all 80 parameter
triples, and the device lane (JAX on the CPU) against the host lane — mixed
dictionary + PLAIN chunks, V2 pages, the overflow proof and its declines.

The table is the benchmark's own corpus kind at a rehearsal size
(benchmark/corpora/tpch_lineitem.py), the reference the benchmark's own
(benchmark/lib/reference_tpch.py: pyarrow.compute over the decimal columns,
and the same sum in Python integers); neither imports the program.
"""

from __future__ import annotations

import datetime
import importlib.util
import json
import sys
from decimal import Decimal
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

jax = pytest.importorskip("jax")

import parquet_tpu.kernels.device_ops  # noqa: E402,F401  (x64 on before any jnp array)

from parquet_tpu.core.reader import FileReader  # noqa: E402
from parquet_tpu.serve import expr  # noqa: E402
from parquet_tpu.serve.aggregate import QueryState, render_query_body, result_dict, run_local_query  # noqa: E402
from parquet_tpu.serve.protocol import (  # noqa: E402
    ServeError,
    agg_name,
    aggregates_from_spec,
    parse_query_request,
)
from parquet_tpu.utils import metrics  # noqa: E402
from parquet_tpu.utils.trace import decode_trace  # noqa: E402

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sys.path.insert(0, str(BENCH / "lib"))  # the corpus kind imports reference_tpch as the corpus's workers do
reference = _load(BENCH / "lib" / "reference_tpch.py")
lineitem = _load(BENCH / "corpora" / "tpch_lineitem.py")
SPEC = json.loads((BENCH / "configs" / "tpch-sf10-lineitem.json").read_text())["corpus"]
SMALL, _ = lineitem.rehearsal(SPEC, 4096)
Q6 = ["count", "sum(l_extendedprice*l_discount)"]
FALLBACK = 'query_device_units_total{engine="host_fallback"}'
DEVICE = 'query_device_units_total{engine="device"}'


def request(path, aggregates, filters=None):
    return parse_query_request(json.dumps({"paths": [str(path)], "aggregates": aggregates, "filters": filters}).encode())


def device_query(path, query) -> dict:
    """Every unit through the executor's device route: a DeviceQueryError is
    the host's unit, counted as the executor counts it."""
    from parquet_tpu.serve.server import ScanService, ServeConfig

    svc = ScanService(ServeConfig(root=str(Path(path).parent), device=True))
    ticket, body = svc.query(query, "test")
    ticket.release()
    return body


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """(path, pyarrow table) of one rehearsal-size lineitem file: three row
    groups, l_extendedprice a mixed dictionary + PLAIN chunk in each."""
    d = tmp_path_factory.mktemp("lineitem")
    lineitem.write_file(SMALL, 2147483777, 0, str(d), [])
    path = d / lineitem.file_name(0)
    return path, pq.read_table(path)


@pytest.fixture(scope="module")
def table_v2(tmp_path_factory):
    d = tmp_path_factory.mktemp("lineitem_v2")
    lineitem.write_file(dict(SMALL, data_page_version="2.0"), 2147483777, 0, str(d), [])
    return d / lineitem.file_name(0)


# -- the wire form ------------------------------------------------------------------

ACCEPTED = [
    (["sum", "l_extendedprice*l_discount"], "sum(l_extendedprice*l_discount)", ("*", ("col", "l_extendedprice"), ("col", "l_discount"))),
    ("sum(l_extendedprice * l_discount)", "sum(l_extendedprice*l_discount)", ("*", ("col", "l_extendedprice"), ("col", "l_discount"))),
    ({"op": "max", "column": "a+b"}, "max(a+b)", ("+", ("col", "a"), ("col", "b"))),
    (["sum", "l_extendedprice*(1-l_discount)*(1+l_tax)"], "sum(l_extendedprice*(1-l_discount)*(1+l_tax))",
     ("*", ("*", ("col", "l_extendedprice"), ("-", ("lit", "1"), ("col", "l_discount"))), ("+", ("lit", "1"), ("col", "l_tax")))),
    (["min", " a - ( b - c ) + d * 2.50 "], "min(a-(b-c)+d*2.50)",
     ("+", ("-", ("col", "a"), ("-", ("col", "b"), ("col", "c"))), ("*", ("col", "d"), ("lit", "2.50")))),
    (["sum", "`net-price`*qty"], "sum(`net-price`*qty)", ("*", ("col", "net-price"), ("col", "qty"))),
    (["sum", "(v)"], "sum(v)", None),
    (["sum", "`a-b`"], "sum(a-b)", None),
    (["sum", "trip distance"], "sum(trip distance)", None),  # no operator character: the column name it always was
    ("count", "count", None),
]


@pytest.mark.parametrize("entry,name,tree", ACCEPTED, ids=[str(a[1]) for a in ACCEPTED])
def test_wire_form_accepted(entry, name, tree):
    (a,) = aggregates_from_spec([entry])
    assert (agg_name(a), a.expr) == (name, tree)
    if tree is not None:
        assert expr.parse(a.column) == tree and expr.render(tree) == a.column


REFUSED = [
    ["sum", "a/b"], ["sum", "a*"], ["sum", "(a*b"], ["sum", "a*b)"], ["sum", "1+2"], ["sum", "a b*c"], ["sum", "a**b"],
    ["sum", "-a*b"], ["sum", "a*1e3"], ["sum", "a*`"], "sum(a", "median(a*b)", ["sum", "*".join(["a"] * 200)],
    ["sum", "a*12345678901234567890"],
]


@pytest.mark.parametrize("entry", REFUSED, ids=[str(e)[:32] for e in REFUSED])
def test_wire_form_refused_with_a_typed_400(entry):
    with pytest.raises(ServeError) as e:
        aggregates_from_spec([entry])
    assert (e.value.status, e.value.code) == (400, "bad_aggregates")


def test_a_forwarded_request_parses_back_to_itself():
    """The mesh router forwards [op, agg_input(a)]: a name that needs
    backticks goes back in them, an expression as its canonical text."""
    from parquet_tpu.serve.protocol import agg_input

    specs = aggregates_from_spec(["count", ["sum", "v"], ["sum", "`a-b`"], ["max", "trip distance"], "min(`a-b`*(c+1.5))"])
    forwarded = [[a.op] if a.column is None else [a.op, agg_input(a)] for a in specs]
    assert forwarded == [["count"], ["sum", "v"], ["sum", "`a-b`"], ["max", "trip distance"], ["min", "`a-b`*(c+1.5)"]]
    assert aggregates_from_spec(forwarded) == specs


def test_expression_over_a_missing_column_is_refused_as_a_missing_column_is(table):
    path, _ = table
    for text in ("no_such", "l_extendedprice*no_such"):
        with pytest.raises(ValueError, match="'no_such' not in schema"):
            run_local_query([str(path)], request(path, [["sum", text]]))


def test_arrow_refuses_what_it_cannot_type(table):
    """A product of two 38-digit products has no decimal128 type, capped or
    not (expr.capped_product: no precision is left for either side): the
    error reads as Arrow's, rendered as the request's 400, on both lanes.
    Q1's charge, which stood here until PR 39, is typed by the cap now
    (tests/test_query_group.py)."""
    path, _ = table
    cube = "l_extendedprice*l_extendedprice*l_extendedprice"
    q = request(path, [["sum", f"({cube})*({cube})"]])
    for run in (lambda: run_local_query([str(path)], q), lambda: device_query(path, q)):
        with pytest.raises(ServeError) as e:
            run()
        assert e.value.status == 400 and "precision" in e.value.message


# -- Q6: host lane = reference = Python integers, device lane = host lane ------------


@pytest.mark.parametrize("k", range(len(reference.PARAMETERS)),
                         ids=[f"{p['date'][:4]}-{p['discount']}-{p['quantity']}" for p in reference.PARAMETERS])
def test_q6_host_lane_equals_both_references(table, k):
    path, t = table
    query = reference.PARAMETERS[k]
    want, ints = reference.q6(t, query), reference.q6_integers(t, query)
    assert want == ints and want["count"] > 0
    body = run_local_query([str(path)], request(path, Q6, reference.filters(query)))
    assert body["result"] == {"count": want["count"], reference.REVENUE: want["revenue"]}
    assert (body["rows_scanned"], body["rows_matched"]) == (t.num_rows, want["count"])
    assert json.loads(render_query_body(body))["result"][reference.REVENUE] == str(want["revenue"])


@pytest.mark.parametrize("k", range(len(reference.PARAMETERS)),
                         ids=[f"{p['date'][:4]}-{p['discount']}-{p['quantity']}" for p in reference.PARAMETERS])
def test_q6_device_lane_equals_host_lane(table, k):
    path, _ = table
    q = request(path, Q6, reference.filters(reference.PARAMETERS[k]))
    snap = metrics.snapshot()
    got = device_query(path, q)
    d = metrics.delta(snap)
    assert render_query_body(got) == render_query_body(run_local_query([str(path)], q))
    assert (d.get(DEVICE, 0), d.get(FALLBACK, 0), d.get("query_expr_overflow_declined", 0)) == (3, 0, 0)
    assert (d["query_expr_units"], d["query_expr_rows"], d["query_mixed_chunks"]) == (3, 3 * SMALL["row_group_rows"], 3)


def test_the_price_chunk_is_mixed_and_merged_on_the_device(table):
    path, _ = table
    meta = pq.ParquetFile(path).metadata
    price = [c for c in range(meta.num_columns) if meta.schema.column(c).name == "l_extendedprice"][0]
    with FileReader(str(path)) as r:
        for g in range(meta.num_row_groups):
            assert {"PLAIN", "RLE_DICTIONARY"} <= set(meta.row_group(g).column(price).encodings)
            dc = r.read_row_group_device(g, ["l_extendedprice", "l_discount"])
            assert dc[("l_extendedprice",)].mixed and not dc[("l_discount",)].mixed


@pytest.mark.parametrize("k", [0, 27, 79])
def test_q6_on_v2_pages(table_v2, table, k):
    q = request(table_v2, Q6, reference.filters(reference.PARAMETERS[k]))
    snap = metrics.snapshot()
    got = device_query(table_v2, q)
    assert metrics.delta(snap).get(FALLBACK, 0) == 0
    want = reference.q6(table[1], reference.PARAMETERS[k])
    assert got["result"] == {"count": want["count"], reference.REVENUE: want["revenue"]}


# -- typed values over the socket ---------------------------------------------------


def test_a_date_as_iso_string_is_the_date(table):
    path, _ = table
    aggs = ["count", ["min", "l_shipdate"], ["max", "l_shipdate"]]
    iso = run_local_query([str(path)], request(path, aggs, [["l_shipdate", ">=", "1994-01-01"], ["l_shipdate", "<", "1995-01-01"]]))
    q = request(path, aggs)._replace(filters=[("l_shipdate", ">=", datetime.date(1994, 1, 1)),
                                              ("l_shipdate", "<", datetime.datetime(1995, 1, 1))])
    assert iso["result"] == run_local_query([str(path)], q)["result"]
    assert iso["result"]["min(l_shipdate)"] >= datetime.date(1994, 1, 1) and iso["result"]["count"] > 0


def test_a_timestamp_as_iso_string_is_the_instant(tmp_path):
    stamps = pa.array(np.arange(10) * 3_600_000_000 + 1_700_000_000_000_000).cast(pa.timestamp("us", tz="UTC"))
    pq.write_table(pa.table({"ts": stamps}), tmp_path / "t.parquet")
    path = tmp_path / "t.parquet"
    at = stamps[4].as_py()
    for text in (at.isoformat(), at.isoformat().replace("+00:00", "Z")):
        assert run_local_query([str(path)], request(path, ["count"], [["ts", ">=", text]]))["result"] == {"count": 6}
    with pytest.raises(ValueError, match="ISO-8601"):
        run_local_query([str(path)], request(path, ["count"], [["ts", ">=", "last tuesday"]]))


def test_a_decimal_bound_as_text_is_exact_and_as_a_float_keeps_its_bracket(table):
    """"0.05" is the decimal 0.05. The JSON number 0.05 is the double
    0.05000000000000000277..., between two representable cents: `>=` it is
    `>= 0.06`, true to the float, not to the query — pinned, not rounded."""
    path, t = table
    cents = reference.unscaled(t["l_discount"])
    count = lambda value: run_local_query(  # noqa: E731
        [str(path)], request(path, ["count"])._replace(filters=[("l_discount", ">=", value)]))["result"]["count"]
    assert count("0.05") == int((cents >= 5).sum()) == count(Decimal("0.05"))
    assert count(0.05) == int((cents >= 6).sum()) < count("0.05")
    assert count(0.06) == int((cents >= 6).sum())  # this double lies below 0.06


# -- DECIMAL and DATE leaves on the device -----------------------------------------

LEAF_AGGS = [["sum", "l_extendedprice"], ["min", "l_extendedprice"], ["max", "l_extendedprice"], ["sum", "l_discount"],
             ["min", "l_shipdate"], ["max", "l_receiptdate"], ["sum", "l_linenumber"], ["sum", "l_quantity*l_tax-l_discount"]]


@pytest.mark.parametrize("agg", LEAF_AGGS, ids=[f"{a[0]}({a[1]})" for a in LEAF_AGGS])
def test_decimal_and_date_aggregates_on_the_device_are_pyarrows(table, agg):
    from parquet_tpu.serve.query_device import device_unit_partial

    path, t = table
    q = request(path, [agg], [["l_quantity", "<", "24"]])
    kept = t.filter(pc.less(t["l_quantity"], pa.scalar(Decimal("24.00"), type=pa.decimal128(15, 2))))
    column = expr.evaluate(expr.parse(agg[1]), kept.column)
    want = getattr(pc, agg[0])(column)
    state = QueryState(q)
    with FileReader(str(path)) as r:
        for g in range(r.num_row_groups):
            state.absorb(device_unit_partial(r, g, q, q.filters))
    assert state.types[0] == want.type, (state.types[0], want.type)
    assert result_dict(q, state, units=3)["result"] == {agg_name(q.aggregates[0]): want.as_py()}


def test_sum_of_a_date_is_refused_on_both_lanes(table):
    path, _ = table
    q = request(path, [["sum", "l_shipdate"]])
    for run in (lambda: run_local_query([str(path)], q), lambda: device_query(path, q)):
        with pytest.raises(ServeError) as e:
            run()
        assert e.value.status == 400


# -- the overflow proof --------------------------------------------------------------


def _decimal_file(path, a, b, **kw):
    cols = {"a": lineitem._decimal(np.asarray(a, dtype=np.int64)), "b": lineitem._decimal(np.asarray(b, dtype=np.int64))}
    pq.write_table(pa.table(cols), path, store_decimal_as_integer=True, **kw)
    return path


def test_statistics_that_fail_the_bound_decline_and_the_answer_stays(tmp_path):
    """max|a| * max|b| * rows reaches 2^63: int64 is not proved enough, so the
    unit is the host's (Arrow sums in 128 bits), counted, and right."""
    a = np.full(1000, 3_000_000_000, dtype=np.int64)
    b = np.full(1000, 3_100_000, dtype=np.int64)  # 9.3e15 a row, 9.3e18 over 1000 rows: past 2^63 = 9.22e18
    path = _decimal_file(tmp_path / "wide.parquet", a, b)
    q = request(path, [["sum", "a*b"], ["max", "a*b"]])
    snap = metrics.snapshot()
    got = device_query(path, q)
    d = metrics.delta(snap)
    assert (d.get("query_expr_overflow_declined", 0), d.get(FALLBACK, 0), d.get(DEVICE, 0)) == (1, 1, 0)
    total = Decimal(int(a[0]) * int(b[0]) * 1000).scaleb(-4)
    assert got["result"] == {"sum(a*b)": total, "max(a*b)": total / 1000} and int(total.scaleb(4)) >= 1 << 63
    # one row fewer a group and the same values are proved: the device answers, the same
    path = _decimal_file(tmp_path / "narrow.parquet", a[:990], b[:990])
    snap = metrics.snapshot()
    got = device_query(path, request(path, [["sum", "a*b"]]))
    d = metrics.delta(snap)
    assert (d.get("query_expr_overflow_declined", 0), d.get(FALLBACK, 0), d.get(DEVICE, 0)) == (0, 0, 1)
    assert got["result"] == {"sum(a*b)": Decimal(int(a[0]) * int(b[0]) * 990).scaleb(-4)}


def test_a_product_past_int64_in_one_row_declines(tmp_path):
    path = _decimal_file(tmp_path / "huge.parquet", [4_000_000_000, -4_000_000_000], [5, 3_000_000_000])
    snap = metrics.snapshot()
    got = device_query(path, request(path, [["min", "a*b"]]))
    assert metrics.delta(snap).get("query_expr_overflow_declined", 0) == 1
    assert got["result"] == {"min(a*b)": Decimal(-12_000_000_000_000_000_000).scaleb(-4)}


def test_a_chunk_without_statistics_declines(tmp_path):
    path = _decimal_file(tmp_path / "bare.parquet", [100, 250], [7, 9], write_statistics=False)
    snap = metrics.snapshot()
    got = device_query(path, request(path, [["sum", "a*b"], ["max", "a"]]))
    d = metrics.delta(snap)
    assert (d.get("query_expr_overflow_declined", 0), d.get(FALLBACK, 0)) == (1, 1)
    assert got["result"] == {"sum(a*b)": Decimal("0.2950"), "max(a)": Decimal("2.50")}
    # min/max of a leaf needs no proof: without the expression the unit engages
    snap = metrics.snapshot()
    assert device_query(path, request(path, [["max", "a"]]))["result"] == {"max(a)": Decimal("2.50")}
    assert metrics.delta(snap).get(DEVICE, 0) == 1


def test_integer_expressions_wrap_nowhere_the_proof_lets_them(tmp_path):
    """int32 * int32 is an Arrow int32: the proof holds the node to 32 bits,
    and where it cannot the host's wrapping kernel answers."""
    v = np.array([50_000, 40_000, -7], dtype=np.int32)
    pq.write_table(pa.table({"v": v, "w": v.astype(np.int64)}), tmp_path / "i.parquet")
    path = tmp_path / "i.parquet"
    for text, declined in (("v*v", 1), ("w*w", 0), ("v*2", 0), ("v+v-w", 0)):
        q = request(path, [["sum", text]])
        snap = metrics.snapshot()
        got = device_query(path, q)
        assert metrics.delta(snap).get("query_expr_overflow_declined", 0) == declined, text
        assert got["result"] == run_local_query([str(path)], q)["result"], text


def test_nulls_under_an_expression_are_the_hosts(tmp_path):
    pq.write_table(pa.table({"a": pa.array([1, None, 3]), "b": pa.array([2, 5, None])}), tmp_path / "n.parquet")
    path = tmp_path / "n.parquet"
    q = request(path, [["sum", "a*b"], ["count", "a"]])
    snap = metrics.snapshot()
    assert device_query(path, q)["result"] == {"sum(a*b)": 2, "count(a)": 2}
    d = metrics.delta(snap)
    assert (d.get(FALLBACK, 0), d.get("query_expr_overflow_declined", 0)) == (1, 0)


def test_group_by_takes_an_expression_on_the_host(table):
    path, t = table
    q = request(path, [["sum", "l_extendedprice*l_discount"]])._replace(group_by=("l_returnflag",))
    got = {g["key"][0]: g["aggregates"][reference.REVENUE] for g in run_local_query([str(path)], q)["groups"]}
    product = pc.multiply(t["l_extendedprice"], t["l_discount"])
    for flag in ("A", "N", "R"):
        assert got[flag] == pc.sum(product.filter(pc.equal(t["l_returnflag"], flag))).as_py()


# -- what a trace shows -------------------------------------------------------------


def test_a_unit_shows_its_four_stages_and_both_scopes(table):
    import re

    import jax.numpy as jnp

    import parquet_tpu.kernels.device_ops as d

    path, _ = table
    q = request(path, Q6, reference.filters(reference.PARAMETERS[0]))
    with decode_trace() as tr:
        device_query(path, q)
    for name in ("serve.aggregate", "query.decode", "query.mask", "query.aggregate", "query.sync"):
        assert tr.stages[name].calls == 3, name
    inside = sum(tr.stages[n].seconds for n in ("query.decode", "query.mask", "query.aggregate", "query.sync"))
    assert inside <= tr.stages["serve.aggregate"].seconds
    mask = jnp.asarray(np.arange(4096) % 3 == 0)
    values = jnp.arange(4096, dtype=jnp.int64)
    for fn, args, kw, scope in (
        (d.expr_agg_device, ((values, values), mask, ("*", ("col", 0), ("col", 1)), "sum"), {}, "pqt.expr_agg"),
        (d.predicate_mask_device, (values, ">=", 5, 5, True), {}, "pqt.query_mask"),
        (d.dict_verdict_device, (mask[:16], values.astype(jnp.int32) % 16), {}, "pqt.query_mask"),
    ):
        names = set(re.findall(r'op_name="([^"]*)"', fn.lower(*args, **kw).compile().as_text()))
        assert any(f"/{scope}/" in f"{n}/" for n in names), (scope, sorted(names)[:6])
