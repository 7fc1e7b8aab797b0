"""The corpus kind tpch_lineitem_q1 writes tpch_lineitem's bytes, its shares
are the Q1 reference's, and the reference agrees with its second witness.

    python -m pytest benchmark/selftest/test_corpora_tpch_q1.py -q        (CPU, host only, seconds)

test_corpora_tpch.py's sibling for the Q1 deployment (a PR of this kind adds
files only); tests/test_benchmark_selftest.py is tier-1's door to this file.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path[:0] = [str(BENCH / "lib")]

from byname import load_by_name  # noqa: E402

q1_kind = load_by_name("corpora", "tpch_lineitem_q1")
q6_kind = load_by_name("corpora", "tpch_lineitem")
reference = load_by_name("lib", "reference_tpch_q1")
CONFIG = json.loads((BENCH / "configs" / "tpch-sf10-pricing-summary.json").read_text())
Q6_CONFIG = json.loads((BENCH / "configs" / "tpch-sf10-lineitem.json").read_text())
SMALL, SCALE = q1_kind.rehearsal(CONFIG["corpus"], 4096)


def test_q1_the_corpus_is_the_q6_tables_key_for_key_except_its_kind():
    mine, theirs = CONFIG["corpus"], Q6_CONFIG["corpus"]
    assert {k: v for k, v in mine.items() if k != "kind"} == {k: v for k, v in theirs.items() if k != "kind"}
    assert (mine["kind"], theirs["kind"]) == ("tpch_lineitem_q1", "tpch_lineitem")
    assert CONFIG["architecture"] is None and len(CONFIG["source"]) <= 200 and list(CONFIG["reduced"]) == ["rows"]
    assert set(Q6_CONFIG["assumed"]) < set(CONFIG["assumed"]), "tpch-sf10-lineitem's own list, and the two rules"
    assert (SMALL, SCALE) == q6_kind.rehearsal(theirs | {"kind": "tpch_lineitem_q1"}, 4096)


def test_q1_the_same_seed_writes_the_q6_kinds_bytes(tmp_path):
    (tmp_path / "q1").mkdir()
    (tmp_path / "q6").mkdir()
    queries = reference.PARAMETERS[:3]
    mine = q1_kind.write_file(SMALL, 2147483777, 5, str(tmp_path / "q1"), queries)
    again = q1_kind.write_file(SMALL, 2147483777, 5, str(tmp_path / "q1"), queries)
    theirs = q6_kind.write_file(SMALL, 2147483777, 5, str(tmp_path / "q6"), [])
    name = q1_kind.file_name(5)
    assert name == q6_kind.file_name(5) and mine == again
    assert (tmp_path / "q1" / name).read_bytes() == (tmp_path / "q6" / name).read_bytes()
    assert (mine["index"], mine["rows"]) == (theirs["index"], theirs["rows"]) == (5, SMALL["rows_per_file"])
    assert mine["shares"] == reference.file_shares(str(tmp_path / "q1" / name), queries)
    other = q1_kind.write_file(SMALL, 2147483778, 5, str(tmp_path / "q1"), queries)
    assert other["shares"] != mine["shares"]


def test_q1_the_references_agree_and_merge_adds_up(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    queries = reference.PARAMETERS[::12]
    files = [q1_kind.write_file(SMALL, 11, i, str(tmp_path), queries) for i in range(2)]
    whole = pa.concat_tables([pq.read_table(tmp_path / q1_kind.file_name(i)) for i in range(2)])
    # what the workers run (one pass, by ship date) is the plain q1, query by query, over all 61 DELTAs
    assert reference.q1_by_ship_date(whole, reference.PARAMETERS) == [reference.q1(whole, q) for q in reference.PARAMETERS]
    for k, q in enumerate(queries):
        want = reference.q1(whole, q)
        assert want == reference.q1_integers(whole, q) and len(want) == 4
        merged = reference.expected({"files": files}, len(queries))[k]
        assert merged == reference.merge([[[f, s, sums] for (f, s), sums in sorted(want.items())]])
        assert [g["key"] for g in merged] == [["A", "F"], ["N", "F"], ["N", "O"], ["R", "F"]]
        for g in merged:
            sums = want[tuple(g["key"])]
            a = g["aggregates"]
            assert list(a) == list(reference.AGGREGATES) and a["count"] == sums["count"]
            assert a["sum(l_extendedprice*(1-l_discount)*(1+l_tax))"] == reference.decimal_text(sums["charge"], 6)
            assert a["avg(l_discount)"] == reference.average_text(sums["discount"], sums["count"], 2)
            assert len(a["avg(l_quantity)"].partition(".")[2]) == 6 and len(a["sum(l_quantity)"].partition(".")[2]) == 2
    # (N, F) is the thin group: shipped on or before 1995-06-17, received after it
    counts = {tuple(g["key"]): g["aggregates"]["count"] for g in merged}
    assert counts[("N", "F")] * 20 < min(counts[("A", "F")], counts[("R", "F")], counts[("N", "O")])
    assert reference.merge([[]]) == [] and len(reference.PARAMETERS) == 61
    assert reference.filters({"delta": "60"}) == [["l_shipdate", "<=", "1998-10-02"]]
    assert reference.filters({"delta": "120"}) == [["l_shipdate", "<=", "1998-08-03"]]


def test_q1_an_average_is_the_merged_sum_over_the_merged_count_half_up():
    one = [["A", "F", {"count": 1, "quantity": 100, "price": 5, "discount": 0, "disc_price": 500, "charge": 50000}]]
    two = [["A", "F", {"count": 99999, "quantity": 200, "price": 0, "discount": 7, "disc_price": 0, "charge": 0}],
           ["N", "O", {"count": 3, "quantity": 1000, "price": 1, "discount": 2, "disc_price": 3, "charge": 4}]]
    (af, no) = reference.merge([one, two])
    assert af["key"] == ["A", "F"] and af["aggregates"]["count"] == 100000
    assert af["aggregates"]["avg(l_extendedprice)"] == "0.000001"  # 0.05 / 100000 = 0.0000005: the tie goes up
    assert af["aggregates"]["avg(l_quantity)"] == "0.000030" and af["aggregates"]["avg(l_discount)"] == "0.000001"
    assert no["aggregates"]["avg(l_quantity)"] == "3.333333" and no["aggregates"]["sum(l_extendedprice*(1-l_discount))"] == "0.0003"
