"""The corpus kind tpch_lineitem keeps dbgen's laws, and the same seed writes
the same bytes.

    python -m pytest benchmark/selftest/test_corpora_tpch.py -q        (CPU, host only, seconds)

test_corpora.py's sibling for the TPC-H table (a PR of this kind adds files
only); tests/test_benchmark_selftest.py is tier-1's door to this file.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path[:0] = [str(BENCH / "lib")]

from byname import load_by_name  # noqa: E402

lineitem = load_by_name("corpora", "tpch_lineitem")
reference = load_by_name("lib", "reference_tpch")
SPEC = json.loads((BENCH / "configs" / "tpch-sf10-lineitem.json").read_text())["corpus"]
SMALL, SCALE = lineitem.rehearsal(SPEC, 4096)


@pytest.fixture(scope="module")
def columns():
    return lineitem.build_columns(SMALL, 2147483777, 3)


def test_lineitem_rehearsal_shrinks_the_table_and_keeps_its_laws():
    assert SCALE == 4096 / SPEC["row_group_rows"]
    assert SMALL == dict(SPEC, row_group_rows=4096, rows_per_file=12288, orders_per_file=4096,
                         dictionary_pagesize_limit=4096, data_page_size=4096)
    assert (SPEC["files"] * SPEC["rows_per_file"], SPEC["parts"], SPEC["suppliers"]) == (37748736, 2000000, 100000)


def test_lineitem_the_same_seed_writes_the_same_bytes(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    queries = reference.PARAMETERS[:3]
    facts = [lineitem.write_file(SMALL, 2147483777, 5, str(tmp_path / d), queries) for d in "ab"]
    assert facts[0] == facts[1] and facts[0]["rows"] == SMALL["rows_per_file"]
    name = lineitem.file_name(5)
    assert (tmp_path / "a" / name).read_bytes() == (tmp_path / "b" / name).read_bytes()
    other = lineitem.write_file(SMALL, 2147483778, 5, str(tmp_path / "b"), queries)
    assert other["shares"] != facts[0]["shares"]


def test_lineitem_one_to_seven_lines_an_order_and_sparse_keys(columns):
    keys, numbers = columns["l_orderkey"], columns["l_linenumber"]
    assert (np.diff(keys) >= 0).all(), "rows in order-key order"
    starts = np.flatnonzero(np.r_[True, np.diff(keys) > 0])
    lines = np.diff(np.r_[starts, len(keys)])
    assert lines.min() >= 1 and lines.max() <= 7 and set(lines[:-1]) == set(range(1, 8))
    assert (numbers[starts] == 1).all() and (numbers == np.arange(len(keys)) - np.repeat(starts, lines) + 1).all()
    assert ((keys - 1) % 32 < 8).all(), "8 of every 32 keys are used"
    assert keys[0] == 3 * SMALL["orders_per_file"] // 8 * 32 + 1, "file 3 goes on where the order numbers say"


def test_lineitem_domains_and_the_price_formula(columns):
    c = columns
    quantity = c["l_quantity"] // 100
    assert (c["l_quantity"] % 100 == 0).all() and (quantity.min(), quantity.max()) == (1, 50)
    assert (c["l_discount"].min(), c["l_discount"].max()) == (0, 10) and (c["l_tax"].min(), c["l_tax"].max()) == (0, 8)
    assert 1 <= c["l_partkey"].min() and c["l_partkey"].max() <= SMALL["parts"]
    p = c["l_partkey"]
    assert (c["l_extendedprice"] == quantity * (90000 + (p // 10) % 20001 + 100 * (p % 1000))).all()
    assert c["l_extendedprice"].max() <= 10494950
    s = SMALL["suppliers"]
    offset = (c["l_suppkey"] - 1 - p) % s  # j x (S/4 + (p - 1)/S) mod S, for one j of 0..3
    step = s // 4 + (p - 1) // s
    hits = (offset[:, None] - np.arange(4) * step[:, None]) % s == 0
    assert hits.any(axis=1).all() and hits.any(axis=0).all(), "every row one of its part's four suppliers, each j drawn"


def test_lineitem_dates_flags_and_status(columns):
    c = columns
    ship, commit, receipt = c["l_shipdate"].astype(np.int64), c["l_commitdate"].astype(np.int64), c["l_receiptdate"].astype(np.int64)
    assert 1 <= (receipt - ship).min() and (receipt - ship).max() <= 30
    # order date = ship - 1..121 = commit - 30..90: the two windows overlap for every row
    assert ((ship - 121 <= commit - 30) & (commit - 90 <= ship - 1)).all()
    assert ship.min() >= lineitem.ORDERDATE_MIN + 1 and ship.max() <= lineitem.ORDERDATE_MAX + 121
    late = receipt > lineitem.CURRENTDATE
    assert (c["l_returnflag"][late] == 2).all() and set(c["l_returnflag"][~late]) == {0, 1}
    assert ((c["l_linestatus"] == 1) == (ship > lineitem.CURRENTDATE)).all()
    starts, lengths = c["l_comment"]
    assert lengths.min() >= 10 and lengths.max() <= 43 and (starts + lengths).max() <= lineitem.POOL_BYTES


def test_lineitem_the_file_is_the_source_schema_with_a_mixed_price_chunk_in_every_group(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    facts = lineitem.write_file(SMALL, 7, 0, str(tmp_path), reference.PARAMETERS[:2])
    file = pq.ParquetFile(tmp_path / lineitem.file_name(0))
    meta, schema = file.metadata, file.schema
    assert meta.num_row_groups == 3 and meta.num_rows == facts["rows"] == SMALL["rows_per_file"]
    assert [schema.column(i).name for i in range(16)] == list(lineitem.COLUMNS)
    assert all(schema.column(i).max_definition_level == 0 for i in range(16)), "every column required"
    by_name = {schema.column(i).name: i for i in range(16)}
    for name in lineitem.DECIMALS:
        col = schema.column(by_name[name])
        assert (col.physical_type, str(col.logical_type)) == ("INT64", "Decimal(precision=15, scale=2)")
    assert schema.column(by_name["l_shipdate"]).physical_type == "INT32"
    for g in range(3):
        price = meta.row_group(g).column(by_name["l_extendedprice"])
        assert {"PLAIN", "RLE_DICTIONARY"} <= set(price.encodings) and price.statistics.has_min_max
        for name in ("l_discount", "l_quantity"):  # small domains: dictionary pages only, no fallback
            col = meta.row_group(g).column(by_name[name])
            assert col.has_dictionary_page and col.statistics.has_min_max
    table = file.read()
    assert table.schema.field("l_extendedprice").type == pa.decimal128(15, 2)
    assert table.equals(lineitem.build_table(SMALL, 7, 0))
    assert facts["shares"] == reference.file_shares(str(tmp_path / lineitem.file_name(0)), reference.PARAMETERS[:2])
    text = table["l_comment"].to_pylist()
    pool = bytes(lineitem.text_pool()).decode()
    assert all(t in pool for t in text[:200])


def test_lineitem_the_references_agree_and_merge_adds_up(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    queries = reference.PARAMETERS[::9]
    files = [lineitem.write_file(SMALL, 11, i, str(tmp_path), queries) for i in range(2)]
    whole = pa.concat_tables([pq.read_table(tmp_path / lineitem.file_name(i)) for i in range(2)])
    for k, q in enumerate(queries):
        want = reference.q6(whole, q)
        assert want == reference.q6_integers(whole, q)
        assert reference.expected({"files": files}, len(queries))[k] == {
            "count": want["count"], reference.REVENUE: str(want["revenue"])}
    assert reference.merge([{"count": 0, "revenue": None}]) == {"count": 0, reference.REVENUE: None}
    assert len(reference.PARAMETERS) == 80 and len({json.dumps(p) for p in reference.PARAMETERS}) == 80
    assert reference.filters({"date": "1994-01-01", "discount": "0.06", "quantity": "24"}) == [
        ["l_shipdate", ">=", "1994-01-01"], ["l_shipdate", "<", "1995-01-01"],
        ["l_discount", ">=", "0.05"], ["l_discount", "<=", "0.07"], ["l_quantity", "<", "24"]]
