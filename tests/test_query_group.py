"""Grouped queries on the device lane, avg, and the 38-digit product cap:
TPC-H Q1 through /v1/query's machinery on the CPU.

Device lane = host lane = the benchmark's plain reference of Q1
(benchmark/lib/reference_tpch_q1.py: pyarrow's filter + group_by, the charge's
products in Python integers) = its second witness (every sum in Python
integers) over all 61 DELTAs, on the benchmark's own corpus kind at a
rehearsal size; then the envelope: key dictionaries in different orders, a
group absent from a unit, zero matching rows, every typed decline counted and
answered by the host with the same bytes, one fetch a unit, a bounded set of
programs, no float in any of them; avg's wire forms and rounding; the cap on
both lanes and its checked-cast 400. Neither reference imports the program.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from decimal import Decimal
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

jax = pytest.importorskip("jax")

import parquet_tpu.kernels.device_ops as device_ops  # noqa: E402  (x64 on before any jnp array)

from parquet_tpu.serve import expr  # noqa: E402
from parquet_tpu.serve.aggregate import render_avg, render_query_body, run_local_query  # noqa: E402
from parquet_tpu.serve.protocol import ServeError, agg_name, aggregates_from_spec, parse_query_request  # noqa: E402
from parquet_tpu.utils import metrics  # noqa: E402
from parquet_tpu.utils.trace import decode_trace  # noqa: E402

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sys.path.insert(0, str(BENCH / "lib"))  # the corpus kind imports its reference as the corpus's workers do
reference = _load(BENCH / "lib" / "reference_tpch_q1.py")
lineitem = _load(BENCH / "corpora" / "tpch_lineitem_q1.py")
SPEC = json.loads((BENCH / "configs" / "tpch-sf10-pricing-summary.json").read_text())["corpus"]
SMALL, _ = lineitem.rehearsal(SPEC, 4096)
Q1 = list(reference.AGGREGATES)
KEYS = list(reference.GROUP_BY)
FALLBACK = 'query_device_units_total{engine="host_fallback"}'
DEVICE = 'query_device_units_total{engine="device"}'


def request(path, aggregates, filters=None, group_by=()):
    return parse_query_request(json.dumps({
        "paths": [str(path)], "aggregates": aggregates, "filters": filters, "group_by": list(group_by)}).encode())


def device_query(path, query) -> dict:
    """Every unit through the executor's device route: a DeviceQueryError is
    the host's unit, counted as the executor counts it."""
    from parquet_tpu.serve.server import ScanService, ServeConfig

    svc = ScanService(ServeConfig(root=str(Path(path).parent), device=True))
    ticket, body = svc.query(query, "test")
    ticket.release()
    return body


def both_lanes(path, query):
    """(host body bytes, device body bytes, the counters' rise under the device run)."""
    host = render_query_body(run_local_query([str(path)], query))
    snap = metrics.snapshot()
    dev = render_query_body(device_query(path, query))
    return host, dev, metrics.delta(snap)


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """(path, pyarrow table) of one rehearsal-size lineitem file: three row
    groups, the two key columns dictionary chunks of 3 and 2 entries."""
    d = tmp_path_factory.mktemp("lineitem_q1")
    lineitem.write_file(SMALL, 2147483777, 0, str(d), [])
    path = d / lineitem.file_name(0)
    return path, pq.read_table(path)


def write(path, columns: dict, **options) -> Path:
    pq.write_table(pa.table(columns), path, **options)
    return path


# -- Q1: device lane = host lane = reference = Python integers, all 61 DELTAs ---------


@pytest.mark.parametrize("k", range(len(reference.PARAMETERS)), ids=[p["delta"] for p in reference.PARAMETERS])
def test_q1_both_lanes_equal_both_references(table, k):
    path, t = table
    query = reference.PARAMETERS[k]
    want, ints = reference.q1(t, query), reference.q1_integers(t, query)
    assert want == ints and len(want) == 4
    groups = reference.merge([[[f, s, sums] for (f, s), sums in sorted(want.items())]])
    q = request(path, Q1, reference.filters(query), KEYS)
    host, dev, d = both_lanes(path, q)
    body = json.loads(host)
    assert body["groups"] == groups and body["group_count"] == 4
    assert [g["key"] for g in groups] == [["A", "F"], ["N", "F"], ["N", "O"], ["R", "F"]]
    assert (body["rows_scanned"], body["rows_matched"]) == (t.num_rows, sum(v["count"] for v in want.values()))
    assert dev == host
    assert (d.get(DEVICE, 0), d.get(FALLBACK, 0), d.get("query_group_declined", 0)) == (3, 0, 0)
    assert (d["query_group_units"], d["query_group_rows"]) == (3, 3 * SMALL["row_group_rows"])
    assert d.get("query_expr_overflow_declined", 0) == 0 and "query_expr_rows" not in d  # the grouped kernel, not expr_agg


def test_the_reference_imports_neither_the_program_nor_jax():
    text = (BENCH / "lib" / "reference_tpch_q1.py").read_text()
    assert "parquet_tpu" not in text.replace("parquet_tpu/serve/expr.py", "") and "import jax" not in text
    assert len(reference.PARAMETERS) == 61 and reference.filters({"delta": "90"}) == [["l_shipdate", "<=", "1998-09-02"]]
    assert reference.average_text(5, 100000, 2) == "0.000001" and reference.average_text(-5, 100000, 2) == "-0.000001"
    assert reference.average_text(1000, 4, 2) == "2.500000" and reference.decimal_text(-1234567, 6) == "-1.234567"


def test_the_cli_gives_the_daemons_bytes(table, capsys):
    from parquet_tpu.tools.parquet_tool import main

    path, _ = table
    filters = reference.filters({"delta": "90"})
    q = request(path, Q1, filters, KEYS)
    assert main(["scan", str(path), "--aggregate", json.dumps(Q1), "--group-by", ",".join(KEYS),
                 "--filters", json.dumps(filters)]) == 0
    assert capsys.readouterr().out.encode() == render_query_body(device_query(path, q))


# -- the envelope of a grouped unit ------------------------------------------------------


@pytest.fixture()
def orders(tmp_path):
    """Two row groups whose key dictionaries come in different orders (a
    dictionary's order is first appearance), the second without group "c";
    a second key, and values with a negative."""
    k = ["b", "a", "c", "a", "b", "c"] + ["a", "b", "b", "a", "a", "b"]
    j = ["x", "y"] * 6
    v = [5, -7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43]
    return write(tmp_path / "orders.parquet", {
        "k": k, "j": j, "v": pa.array(v, pa.int64()), "w": pa.array(v, pa.int32()),
        "m": pa.array([Decimal(x) / 100 for x in v], pa.decimal128(9, 2))},
        row_group_size=6, store_decimal_as_integer=True)


GROUPED = ["count", "sum(v)", "min(v)", "max(v)", "avg(v)", "count(v)", "min(w)", "avg(w)", "sum(m)", "min(m)",
           "avg(m)", "sum(v*w)", "max(v*(10-w))"]


def test_key_dictionaries_in_different_orders_and_a_group_absent_from_a_unit(orders):
    from parquet_tpu.core.reader import FileReader

    with FileReader(str(orders)) as r:
        firsts = [r.read_row_group_device(g, ["k"])[("k",)].dictionary.to_list() for g in range(2)]
    assert firsts == [[b"b", b"a", b"c"], [b"a", b"b"]]
    for keys in (["k"], ["k", "j"], ["j", "k"]):
        host, dev, d = both_lanes(orders, request(orders, GROUPED, None, keys))
        assert dev == host and (d[DEVICE], d["query_group_units"], d.get("query_group_declined", 0)) == (2, 2, 0)
    body = json.loads(host)
    assert body["group_count"] == 6 and body["groups"][0] == {"key": ["x", "a"], "aggregates": {
        "count": 2, "sum(v)": 64, "min(v)": 23, "max(v)": 41, "avg(v)": "32.0000", "count(v)": 2, "min(w)": 23,
        "avg(w)": "32.0000", "sum(m)": "0.64", "min(m)": "0.23", "avg(m)": "0.320000", "sum(v*w)": 23 * 23 + 41 * 41,
        "max(v*(10-w))": 23 * (10 - 23)}}


def test_zero_matching_rows_is_no_group_and_a_filtered_unit_drops_its_empty_slots(orders):
    q = request(orders, GROUPED, [["v", "==", 12]], ["k"])  # inside the first group's statistics, in no row
    host, dev, d = both_lanes(orders, q)
    assert dev == host and json.loads(dev)["groups"] == [] and json.loads(dev)["group_count"] == 0
    assert (json.loads(dev)["units"], d["query_group_units"]) == (1, 1)
    host, dev, _ = both_lanes(orders, request(orders, GROUPED, [["v", ">=", 17], ["v", "<", 30]], ["k"]))
    assert dev == host and [g["key"] for g in json.loads(dev)["groups"]] == [["a"], ["b"], ["c"]]


def test_one_device_get_a_unit(table, monkeypatch):
    path, _ = table
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    with decode_trace() as tr:
        device_query(path, request(path, Q1, reference.filters({"delta": "90"}), KEYS))
    assert len(calls) == 3 and tr.stages["query.sync"].calls == 3
    keys = tr.stages["query.group_keys"]  # all of it inside query.aggregate
    assert keys.calls == 3 and 0 < keys.seconds == keys.nested_seconds <= tr.stages["query.aggregate"].seconds


def _decline_cases(tmp_path):
    rng = np.random.default_rng(39)
    n = 6000
    v = pa.array(rng.integers(0, 100, n), pa.int64())
    few = pa.array([("k%d" % i) for i in rng.integers(0, 5, n)])
    long_keys = [("k%02d" % i) * 40 for i in range(40)]
    spill = pa.array([long_keys[i] for i in np.concatenate([rng.integers(0, 2, n // 2), rng.integers(0, 40, n // 2)])])
    holes = pa.array([None if i % 7 == 0 else "k%d" % (i % 3) for i in range(n)])
    many = pa.array(["k%d" % i for i in rng.integers(0, 65, n)])
    money = pa.array([Decimal(int(x)) / 100 for x in rng.integers(0, 10**6, n)], pa.decimal128(15, 2))
    huge = pa.array([Decimal(int(x)) * 10**12 for x in rng.integers(1, 9, n)], pa.decimal128(18, 0))
    return {
        "plain_key": ("key_not_dictionary", write(tmp_path / "plain.parquet", {"k": few, "v": v}, use_dictionary=False), ["sum(v)"]),
        "mixed_key": ("key_not_dictionary", write(tmp_path / "mixed.parquet", {"k": spill, "v": v},
                                                  dictionary_pagesize_limit=2048, data_page_size=1024), ["sum(v)"]),
        "numeric_key": ("key_not_dictionary", write(tmp_path / "numeric.parquet", {"k": pa.array(rng.integers(0, 5, n)), "v": v}), ["sum(v)"]),
        "nulls_in_a_key": ("key_nulls", write(tmp_path / "holes.parquet", {"k": holes, "v": v}), ["sum(v)", "count"]),
        "too_many_groups": ("too_many_groups", write(tmp_path / "many.parquet", {"k": many, "v": v}), ["sum(v)"]),
        "nulls_in_an_input": ("input_shape", write(tmp_path / "input.parquet", {
            "k": few, "v": pa.array([None if i % 5 == 0 else i for i in range(n)], pa.int64())}), ["sum(v)", "avg(v)", "count(v)"]),
        "no_statistics": (None, write(tmp_path / "nostats.parquet", {"k": few, "m": money}, write_statistics=False,
                                      store_decimal_as_integer=True), ["sum(m)", "avg(m)"]),
        "overflow": (None, write(tmp_path / "overflow.parquet", {"k": few, "h": huge}, store_decimal_as_integer=True),
                     ["sum(h*h)"]),
    }


@pytest.mark.parametrize("case", ["plain_key", "mixed_key", "numeric_key", "nulls_in_a_key", "too_many_groups",
                                  "nulls_in_an_input", "no_statistics", "overflow"])
def test_each_decline_is_typed_counted_and_answered_by_the_host_with_the_same_bytes(tmp_path, case):
    reason, path, aggregates = _decline_cases(tmp_path)[case]
    if case == "mixed_key":
        encodings = set(pq.ParquetFile(path).metadata.row_group(0).column(0).encodings)
        if not {"PLAIN", "RLE_DICTIONARY"} <= encodings:
            pytest.skip(f"pyarrow no longer mixes page encodings ({encodings})")
    host, dev, d = both_lanes(path, request(path, aggregates, None, ["k"]))
    assert dev == host and json.loads(dev)["group_count"] > 0
    assert (d.get(DEVICE, 0), d[FALLBACK], d.get("query_group_units", 0)) == (0, 1, 0)
    if reason is None:  # the proof's own decline, as for a global aggregate
        assert (d["query_expr_overflow_declined"], d.get("query_group_declined", 0)) == (1, 0)
    else:
        assert (d["query_group_declined"], d[f'query_group_decline_reasons_total{{reason="{reason}"}}']) == (1, 1)


def test_sixty_four_groups_fit_the_bucket(tmp_path):
    rng = np.random.default_rng(7)
    n = 5000
    path = write(tmp_path / "full.parquet", {
        "a": pa.array(["a%d" % i for i in rng.integers(0, 8, n)]), "b": pa.array(["b%d" % i for i in rng.integers(0, 8, n)]),
        "v": pa.array(rng.integers(-1000, 1000, n), pa.int64())})
    host, dev, d = both_lanes(path, request(path, ["count", "sum(v)", "min(v)", "max(v)", "avg(v)"], None, ["a", "b"]))
    assert dev == host and json.loads(dev)["group_count"] == device_ops.GROUP_SLOTS == 64 and d["query_group_units"] == 1


def test_a_second_file_lowers_no_new_program(tmp_path):
    """The grouped kernel compiles per (programs, rows): dictionary sizes,
    their order and the groups present reach it as data."""
    from jax import monitoring

    def file(name, seed, flags, statuses):
        rng = np.random.default_rng(seed)
        n = 4096
        return write(tmp_path / name, {
            "f": pa.array([flags[i] for i in rng.integers(0, len(flags), n)]),
            "s": pa.array([statuses[i] for i in rng.integers(0, len(statuses), n)]),
            "p": pa.array([Decimal(int(x)) / 100 for x in rng.integers(100, 10**7, n)], pa.decimal128(15, 2)),
            "d": pa.array([Decimal(int(x)) / 100 for x in rng.integers(0, 11, n)], pa.decimal128(15, 2))},
            store_decimal_as_integer=True)

    aggregates = ["sum(p)", "sum(p*(1-d))", "avg(d)", "count"]
    lowered: list = []

    def listen(name, seconds, **kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(kw.get("fun_name"))

    monitoring.register_event_duration_secs_listener(listen)
    try:
        first = file("a.parquet", 1, "RAN", "FO")
        device_query(first, request(first, aggregates, None, ["f", "s"]))
        assert "jit(group_agg_device)" in lowered
        lowered.clear()
        # 4 x 2 slots in another order where the first had 3 x 2: what is lowered again is the decode's
        # (a dictionary of another length), never the grouped kernel or anything after it
        second = file("b.parquet", 2, "NXRA", "OF")
        host, dev, d = both_lanes(second, request(second, aggregates, None, ["f", "s"]))
        assert dev == host and d["query_group_units"] == 1 and json.loads(dev)["group_count"] == 8
        assert set(lowered) <= {"jit(dict_gather_device)"}, lowered
        third = file("c.parquet", 3, "NXRAB", "OFPQRST")  # 5 x 7 slots: wider index streams, the same grouped program
        host, dev, _ = both_lanes(third, request(third, aggregates, None, ["f", "s"]))
        assert dev == host and json.loads(dev)["group_count"] == 35 and "jit(group_agg_device)" not in lowered
    finally:
        monitoring.unregister_event_duration_listener(listen)


def _avals(jaxpr):
    for v in [*jaxpr.invars, *jaxpr.outvars, *jaxpr.constvars]:
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in [*eqn.invars, *eqn.outvars]:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_no_query_program_holds_a_float(table, monkeypatch):
    """Every program jax lowers while Q1 is answered — the decode kernels,
    the mask, the grouped kernel, the eager one-primitive programs — is caught
    at the lowering seam and its jaxpr walked (as
    tests/test_device_doubles.py::TestNoFloat64 does): one floating value
    anywhere fails."""
    import jax.numpy as jnp
    from jax._src.interpreters import mlir

    path, _ = table
    seen = []
    real = mlir.lower_jaxpr_to_module

    def spy(module_name, jaxpr, *a, **kw):
        seen.append((module_name, jaxpr))
        return real(module_name, jaxpr, *a, **kw)

    monkeypatch.setattr(mlir, "lower_jaxpr_to_module", spy)
    jax.clear_caches()
    try:
        device_query(path, request(path, Q1, reference.filters({"delta": "90"}), KEYS))
        device_query(path, request(path, ["avg(l_quantity)", "sum(l_extendedprice*(1-l_discount)*(1+l_tax))"]))
    finally:
        jax.clear_caches()
    names = {n for n, _ in seen}
    assert {"jit(group_agg_device)", "jit(expr_agg_device)", "jit(expand_hybrid_device)"} <= names, names
    bad = sorted({(n, str(a)) for n, j in seen for a in _avals(j.jaxpr)
                  if jnp.issubdtype(getattr(a, "dtype", jnp.int32), jnp.floating)})
    assert not bad, f"floating values in query programs: {bad}"


def test_the_kernel_alone_against_numpy():
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    n = 3000
    a, b = rng.integers(0, 5, n).astype(np.int32), rng.integers(0, 3, n).astype(np.int32)
    x, y = rng.integers(-10**9, 10**9, n), rng.integers(0, 100, n)
    mask = rng.random(n) < 0.7
    programs = ((("col", 0), ("sum", "min", "max")), (("*", ("col", 0), ("-", ("lit", 100), ("col", 1))), ("sum",)))
    counts, reduced = jax.device_get(device_ops.group_agg_device(
        (jnp.asarray(a), jnp.asarray(b)), np.asarray([15, 3, 1], np.int32), (jnp.asarray(x), jnp.asarray(y)),
        jnp.asarray(mask), programs))
    assert counts.shape == (device_ops.GROUP_SLOTS,) and counts[15:].sum() == 0
    for s in range(15):
        here = mask & (a * 3 + b == s)
        assert counts[s] == here.sum() > 0
        assert [int(r[s]) for r in reduced[0]] == [x[here].sum(), x[here].min(), x[here].max()]
        assert int(reduced[1][0][s]) == (x[here] * (100 - y[here])).sum()


# -- avg ------------------------------------------------------------------------------------


@pytest.mark.parametrize("entry,name", [
    (["avg", "v"], "avg(v)"), ("avg(v)", "avg(v)"), ({"op": "avg", "column": "v"}, "avg(v)"),
    ("avg(a*(1-b))", "avg(a*(1-b))"), (["avg", "`a-b`"], "avg(a-b)")])
def test_avg_wire_forms(entry, name):
    (a,) = aggregates_from_spec([entry])
    assert a.op == "avg" and agg_name(a) == name


@pytest.mark.parametrize("entry", [["avg"], "avg", "avg()", ["avg", "a/b"]])
def test_avg_needs_an_input(entry):
    with pytest.raises(ServeError) as e:
        aggregates_from_spec([entry])
    assert (e.value.status, e.value.code) == (400, "bad_aggregates")


TIES = [
    ((Decimal("0.05"), 100000), pa.decimal128(38, 2), "0.000001"),    # 0.0000005: the tie goes up
    ((Decimal("-0.05"), 100000), pa.decimal128(38, 2), "-0.000001"),  # and away from zero
    ((Decimal("0.04"), 100000), pa.decimal128(38, 2), "0.000000"),
    ((1, 20000), pa.int64(), "0.0001"), ((-1, 20000), pa.int64(), "-0.0001"), ((1, 20001), pa.int64(), "0.0000"),
    ((7, 2), pa.int64(), "3.5000"), ((2, 3), pa.uint64(), "0.6667"),
    ((Decimal("12345678901234567890123456789012345.67"), 3), pa.decimal128(38, 2), "4115226300411522630041152263004115.223333"),
    (None, None, None),
]


@pytest.mark.parametrize("pair,typ,text", TIES, ids=[str(t[2]) for t in TIES])
def test_avg_is_rendered_once_half_up_at_scale_plus_four(pair, typ, text):
    assert render_avg(pair, typ) == text


def test_avg_is_the_merged_sum_over_the_merged_count_on_both_lanes(tmp_path):
    """Two units whose own averages are 1 and 3.3333: the average of the
    averages is 2.1667, the answer 15 / 8. An optional input counts its
    non-null values. Zero rows: null. A float input: a typed 400, never a
    float quotient."""
    v = [1, 1, 1, 1, 1, 2, 2, 6]
    path = write(tmp_path / "avg.parquet", {
        "v": pa.array(v, pa.int64()), "m": pa.array([Decimal(x) / 100 for x in v], pa.decimal128(9, 2)),
        "o": pa.array([None if x == 2 else x for x in v], pa.int32()), "x": pa.array([float(x) for x in v])},
        row_group_size=5, store_decimal_as_integer=True)
    host, dev, d = both_lanes(path, request(path, ["avg(v)", "avg(m)", "avg(o)", "count(o)", "avg(v*v)"]))
    assert dev == host and d[DEVICE] == 2
    assert json.loads(dev)["result"] == {"avg(v)": "1.8750", "avg(m)": "0.018750", "avg(o)": "1.8333", "count(o)": 6,
                                         "avg(v*v)": "6.1250"}
    host, dev, _ = both_lanes(path, request(path, ["avg(v)", "avg(m)", "count"], [["v", ">", 100]]))
    assert dev == host and json.loads(dev)["result"] == {"avg(v)": None, "avg(m)": None, "count": 0}
    for run in (run_local_query, lambda paths, q: device_query(path, q)):
        with pytest.raises(ServeError) as e:
            run([str(path)], request(path, ["avg(x)"]))
        assert (e.value.status, e.value.code) == (400, "bad_aggregates") and "exact" in e.value.message


# -- a product past 38 digits ----------------------------------------------------------------

CHARGES = ["l_extendedprice*(1-l_discount)*(1+l_tax)", "l_extendedprice*(1.00-l_discount)*(1.00+l_tax)"]


@pytest.mark.parametrize("text", CHARGES, ids=["integer literals (61 digits)", "decimal literals (49 digits)"])
def test_the_charge_is_typed_by_the_cap_and_exact_on_both_lanes(table, text):
    path, t = table
    price, discount, tax = (reference.unscaled(t[c]).tolist() for c in ("l_extendedprice", "l_discount", "l_tax"))
    total = sum(p * (100 - d) * (100 + x) for p, d, x in zip(price, discount, tax))
    empty = {c: pa.array([], pa.decimal128(15, 2)) for c in ("l_extendedprice", "l_discount", "l_tax")}
    assert expr.evaluate(expr.parse(text), empty.__getitem__).type == pa.decimal128(38, 6)
    host, dev, d = both_lanes(path, request(path, [f"sum({text})", f"max({text})", f"avg({text})"]))
    assert dev == host and (d[DEVICE], d.get(FALLBACK, 0)) == (3, 0)
    got = json.loads(dev)["result"]
    assert got[f"sum({text})"] == reference.decimal_text(total, 6)
    assert got[f"avg({text})"] == reference.average_text(total, t.num_rows, 6)
    assert got[f"max({text})"] == reference.decimal_text(max(p * (100 - d) * (100 + x) for p, d, x in zip(price, discount, tax)), 6)


def test_the_cap_narrows_the_wider_operand_and_says_where_there_is_none():
    dec = pa.decimal128
    assert expr.capped_product(dec(15, 2), dec(22, 2)) is None  # 38 digits: Arrow's own
    assert expr.capped_product(dec(32, 4), dec(16, 2)) == (0, dec(21, 4))
    assert expr.capped_product(dec(16, 2), dec(32, 4)) == (1, dec(21, 4))
    assert expr.capped_product(dec(38, 4), pa.int64()) == (0, dec(18, 4))  # int64 counts as decimal(19, 0)
    assert expr.capped_product(pa.int32(), dec(38, 0)) == (1, dec(27, 0))
    assert expr.capped_product(pa.int64(), pa.int64()) is None and expr.capped_product(dec(20, 2), pa.float64()) is None
    with pytest.raises(pa.ArrowInvalid, match="precision"):
        expr.capped_product(dec(38, 6), dec(38, 6))
    with pytest.raises(pa.ArrowInvalid, match="precision"):
        expr.capped_product(dec(38, 20), dec(30, 2))  # 7 digits cannot hold scale 20


def test_a_value_past_the_narrower_precision_is_a_400_on_both_lanes_never_a_rounding(tmp_path):
    """big*big is decimal(37, 0); times big again the cap leaves the product's
    operand decimal(19, 0), and 10^24 does not fit it: the host's checked cast
    raises, the device's proof declines the unit to the host, both 400. With
    small values the same tree is exact on both lanes."""
    def file(name, values):
        return write(tmp_path / name, {"b": pa.array([Decimal(v) for v in values], pa.decimal128(18, 0))},
                     store_decimal_as_integer=True)

    q = ["sum(b*b*b)"]
    big = file("big.parquet", [10**12, 2 * 10**12])
    for run in (lambda: run_local_query([str(big)], request(big, q)), lambda: device_query(big, request(big, q))):
        with pytest.raises(ServeError) as e:
            run()
        assert e.value.status == 400 and "does not fit in precision" in e.value.message
    small = file("small.parquet", [123456, -654321])
    host, dev, d = both_lanes(small, request(small, q))
    assert dev == host and d[DEVICE] == 1
    assert json.loads(dev)["result"] == {"sum(b*b*b)": str(123456**3 - 654321**3)}
    # between the two: int64 holds the product, decimal(19, 0) its operand, but the statistics cannot say so
    edge = file("edge.parquet", [2 * 10**6, 3])
    host, dev, d = both_lanes(edge, request(edge, q))
    assert dev == host and json.loads(dev)["result"] == {"sum(b*b*b)": str(8 * 10**18 + 27)}
    assert (d.get(DEVICE, 0), d[FALLBACK], d["query_expr_overflow_declined"]) == (0, 1, 1)
