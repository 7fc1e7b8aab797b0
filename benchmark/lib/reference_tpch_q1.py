"""The plain reference of TPC-H Q1, the Pricing Summary Report Query (clause
2.4.1), over LINEITEM files. numpy + pyarrow only: never imports the program,
never jax.

    select l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice*(1-l_discount)), sum(l_extendedprice*(1-l_discount)*(1+l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem where l_shipdate <= date '1998-12-01' - interval '[DELTA]' day
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus

A query is its substitution parameter as text, {"delta": "90"}: DELTA in
60..120 (`PARAMETERS`: 61 values; validation 90). `q1` answers one table with
pyarrow — a filter on the DATE column, then `group_by` for the counts and the
sums Arrow can type (quantity, price, discount: decimal128 sums; disc_price:
decimal128(15,2) x decimal128(22,2) = decimal128(38,4)); the charge,
l_extendedprice*(1-l_discount)*(1+l_tax), needs 61 digits under Arrow's rule
(49 with decimal literals) and Arrow refuses it, so its per-row products are
taken in Python integers over the unscaled columns (cents x (100 - discount) x
(100 + tax): scale 6), once a file, and handed to Arrow as a decimal128(38, 6)
column that it only adds (`with_charge`). `q1_by_ship_date` answers every
query of a list in one pass (group_by over flag, status and ship date, then
each query's dates added up): what the corpus workers run, held to `q1` query
by query by the tests. `q1_integers` is the second
witness: every sum in Python integers over the unscaled values, the groups by
numpy over the flag and status bytes. A table's answers add up over its files
as Python ints (`merge`); an average is the merged sum over the merged count,
`Decimal.quantize(..., ROUND_HALF_UP)` at the input's scale + 4 (Spark's rule
for avg over DECIMAL(p, s): DECIMAL(p + 4, s + 4)); every value is rendered
as the daemon renders it: a decimal's text. Each corpus worker answers its own
file's share of every query from pyarrow's read of the file it has just
written (`file_shares`): the reference reads what the program will read.
"""

from __future__ import annotations

import datetime
from decimal import ROUND_HALF_UP, Context, Decimal

COLUMNS = ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice", "l_discount", "l_tax", "l_shipdate")
GROUP_BY = ("l_returnflag", "l_linestatus")
AGGREGATES = (
    "sum(l_quantity)", "sum(l_extendedprice)", "sum(l_extendedprice*(1-l_discount))",
    "sum(l_extendedprice*(1-l_discount)*(1+l_tax))", "avg(l_quantity)", "avg(l_extendedprice)", "avg(l_discount)",
    "count",
)
# a share's sums, unscaled, with the scale each is rendered at
SUMS = (("quantity", 2), ("price", 2), ("discount", 2), ("disc_price", 4), ("charge", 6))
PARAMETERS = [{"delta": str(d)} for d in range(60, 121)]
END = datetime.date(1998, 12, 1)
EPOCH = datetime.date(1970, 1, 1)
EXACT = Context(prec=80)  # more digits than any sum here has: no operation rounds but quantize


def ship_until(query: dict) -> datetime.date:
    """The last ship date the query keeps."""
    return END - datetime.timedelta(days=int(query["delta"]))


def filters(query: dict) -> list:
    """Q1's predicate as the one [column, op, value] triple a request
    carries: the DATE bound as an ISO string."""
    return [["l_shipdate", "<=", ship_until(query).isoformat()]]


def unscaled(column):
    """A decimal128 column of precision <= 18 as its unscaled int64 values."""
    import numpy as np

    return np.concatenate([np.frombuffer(c.buffers()[1], dtype=np.int64)[2 * c.offset:2 * (c.offset + len(c)):2]
                           for c in column.chunks] or [np.zeros(0, dtype=np.int64)])


def _unscaled_decimal(value: Decimal, scale: int) -> int:
    return int(value.scaleb(scale, context=EXACT))


def with_charge(table):
    """`table` with the charge as a column: each row's product taken in
    Python integers over the unscaled columns (cents x (100 - discount) x
    (100 + tax): scale 6), 2^18 rows at a time, handed to Arrow as
    decimal128(38, 6) by its words — the low one, and its sign in the high
    one — so that Arrow only ever ADDS it."""
    import numpy as np
    import pyarrow as pa

    price, discount, tax = (unscaled(table[c]) for c in ("l_extendedprice", "l_discount", "l_tax"))
    words = np.empty((table.num_rows, 2), dtype=np.int64)
    for lo in range(0, table.num_rows, 1 << 18):
        rows = slice(lo, lo + (1 << 18))
        charge = [p * (100 - d) * (100 + x)
                  for p, d, x in zip(price[rows].tolist(), discount[rows].tolist(), tax[rows].tolist())]
        if not -(1 << 63) <= min(charge) <= max(charge) < 1 << 63:
            raise ValueError("reference_tpch_q1: a row's charge does not fit the low word")
        words[rows, 0] = charge
    words[:, 1] = words[:, 0] >> 63
    column = pa.Array.from_buffers(pa.decimal128(38, 6), table.num_rows, [None, pa.py_buffer(words)])
    return table.append_column("charge", column)


def _sums(res: dict, g: int) -> dict:
    return {
        "count": res["count_all"][g],
        "quantity": _unscaled_decimal(res["l_quantity_sum"][g], 2),
        "price": _unscaled_decimal(res["l_extendedprice_sum"][g], 2),
        "discount": _unscaled_decimal(res["l_discount_sum"][g], 2),
        "disc_price": _unscaled_decimal(res["disc_price_sum"][g], 4),
        "charge": _unscaled_decimal(res["charge_sum"][g], 6),
    }


MEASURES = [([], "count_all"), ("l_quantity", "sum"), ("l_extendedprice", "sum"), ("l_discount", "sum"),
            ("disc_price", "sum"), ("charge", "sum")]


def _with_products(table):
    """`table` with both expression columns: the charge (with_charge) and
    disc_price, which Arrow types itself: decimal128(15,2) x decimal128(22,2)
    = decimal128(38,4)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if "charge" not in table.column_names:
        table = with_charge(table)
    disc_price = pc.multiply(table["l_extendedprice"], pc.subtract(pa.scalar(1), table["l_discount"]))
    return table.append_column("disc_price", disc_price)


def q1(table, query: dict) -> dict:
    """{(flag, status): {"count", and each of SUMS unscaled}} of the groups
    present, by pyarrow's filter and group_by over the decimal columns, the
    two products among them."""
    import pyarrow as pa
    import pyarrow.compute as pc

    t = _with_products(table)
    t = t.filter(pc.less_equal(t["l_shipdate"], pa.scalar(ship_until(query), type=pa.date32())))
    res = t.group_by(list(GROUP_BY)).aggregate(MEASURES).to_pydict()
    return {(res["l_returnflag"][g], res["l_linestatus"][g]): _sums(res, g) for g in range(len(res["count_all"]))}


def q1_by_ship_date(table, queries: list) -> list:
    """q1 of every query in ONE pass over the table: pyarrow's group_by over
    (flag, status, ship date) first — a few thousand rows of exact sums —
    then each query keeps the dates up to its own bound and adds them up in
    Python integers. What a corpus worker runs (sixteen filtered copies of a
    3-million-row table are a quarter of a minute and gigabytes of churn);
    the tests hold it to q1, query by query."""
    res = _with_products(table).group_by([*GROUP_BY, "l_shipdate"]).aggregate(MEASURES).to_pydict()
    out = []
    for query in queries:
        until, groups = ship_until(query), {}
        for g, day in enumerate(res["l_shipdate"]):
            if day <= until:
                into = groups.setdefault((res["l_returnflag"][g], res["l_linestatus"][g]), {})
                for k, v in _sums(res, g).items():
                    into[k] = into.get(k, 0) + v
        out.append(groups)
    return out


def q1_integers(table, query: dict) -> dict:
    """The same answer wholly in Python integers over the unscaled values."""
    import numpy as np

    ship = table["l_shipdate"].cast("int32").to_numpy()
    keep = ship <= (ship_until(query) - EPOCH).days
    flag = np.asarray(table["l_returnflag"].to_pylist())
    status = np.asarray(table["l_linestatus"].to_pylist())
    quantity, price, discount, tax = (unscaled(table[c]) for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    out = {}
    for f in sorted(set(flag.tolist())):
        for s in sorted(set(status.tolist())):
            rows = np.flatnonzero(keep & (flag == f) & (status == s))
            if not len(rows):
                continue
            q, p, d, x = (a[rows].tolist() for a in (quantity, price, discount, tax))
            out[(f, s)] = {
                "count": len(rows), "quantity": sum(q), "price": sum(p), "discount": sum(d),
                "disc_price": sum(pi * (100 - di) for pi, di in zip(p, d)),
                "charge": sum(pi * (100 - di) * (100 + xi) for pi, di, xi in zip(p, d, x)),
            }
    return out


def file_shares(path: str, queries: list) -> list:
    """One file's share of each query, JSON-ready: a list of groups, each
    [flag, status, {count and unscaled sums}]."""
    import pyarrow.parquet as pq

    table = pq.read_table(path, columns=list(COLUMNS))
    return [[[f, s, sums] for (f, s), sums in sorted(share.items())] for share in q1_by_ship_date(table, queries)]


def decimal_text(unscaled_value: int, scale: int) -> str:
    """An unscaled integer as the daemon renders a decimal of that scale."""
    return str(Decimal(unscaled_value).scaleb(-scale, context=EXACT))


def average_text(unscaled_sum: int, count: int, scale: int) -> str:
    """sum / count at scale + 4, rounded half up, as fixed-point text."""
    total = Decimal(unscaled_sum).scaleb(-scale, context=EXACT)
    quotient = EXACT.divide(total, Decimal(count))
    return format(quotient.quantize(Decimal(1).scaleb(-(scale + 4)), rounding=ROUND_HALF_UP, context=EXACT), "f")


def merge(shares: list) -> list:
    """The shares of one query added up: the daemon's `groups` for it — key
    order, every sum and average as text, the count as an integer."""
    total: dict = {}
    for share in shares:
        for f, s, sums in share:
            into = total.setdefault((f, s), dict.fromkeys(sums, 0))
            for k, v in sums.items():
                into[k] += v
    groups = []
    for (f, s), t in sorted(total.items()):
        sums = {name: decimal_text(t[name], scale) for name, scale in SUMS}
        groups.append({"key": [f, s], "aggregates": {
            AGGREGATES[0]: sums["quantity"], AGGREGATES[1]: sums["price"], AGGREGATES[2]: sums["disc_price"],
            AGGREGATES[3]: sums["charge"],
            AGGREGATES[4]: average_text(t["quantity"], t["count"], 2),
            AGGREGATES[5]: average_text(t["price"], t["count"], 2),
            AGGREGATES[6]: average_text(t["discount"], t["count"], 2),
            AGGREGATES[7]: t["count"],
        }})
    return groups


def expected(facts: dict, n_queries: int) -> list:
    """One merged answer per query, from the corpus facts."""
    return [merge([f["shares"][q] for f in facts["files"]]) for q in range(n_queries)]
