"""TPC-H Q1, the Pricing Summary Report Query, as one POST /v1/query call:
grouped by two flag columns, five sums — one of them the charge,
l_extendedprice*(1-l_discount)*(1+l_tax), a product Arrow's precision rule
cannot type (61 digits) and parquet_tpu/serve/expr.py's cap types as
decimal128(38, 6), computed exactly — and three averages, each the exact
(sum, count) pair divided once and rendered at the input's scale + 4, half
up. With ServeConfig(device=...) every row group is decoded, masked, GROUPED
by the flag columns' resident dictionary indices and reduced in device memory
(one program a unit), in integers proved inside int64 from the chunks' own
statistics; the answer is the same byte for byte with or without a device."""

import sys as _sys
from pathlib import Path as _Path

_sys.path.insert(0, str(_Path(__file__).resolve().parent.parent))

import datetime
import json
import tempfile
import urllib.request
from decimal import ROUND_HALF_UP, Decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from parquet_tpu.serve import ScanServer, ServeConfig
from parquet_tpu.utils import metrics

rng = np.random.default_rng(1)
n = 200_000
epoch = datetime.date(1970, 1, 1)
days = rng.integers((datetime.date(1992, 1, 2) - epoch).days, (datetime.date(1998, 12, 1) - epoch).days, n)
quantity, discount, tax = rng.integers(1, 51, n), rng.integers(0, 11, n), rng.integers(0, 9, n)
price = quantity * rng.integers(90_000, 200_000, n)
flag, status = rng.integers(0, 3, n), rng.integers(0, 2, n)
cents = lambda v: pa.array([Decimal(int(x)).scaleb(-2) for x in v], type=pa.decimal128(15, 2))  # noqa: E731
table = pa.table({
    "l_returnflag": pa.array(["R", "A", "N"]).take(pa.array(flag)),
    "l_linestatus": pa.array(["F", "O"]).take(pa.array(status)),
    "l_shipdate": pa.array(days.astype(np.int32)).cast(pa.date32()),
    "l_quantity": cents(quantity * 100), "l_extendedprice": cents(price),
    "l_discount": cents(discount), "l_tax": cents(tax),
})
root = tempfile.mkdtemp()
# decimals as INT64, as Spark and DuckDB write them: the device lane's form
pq.write_table(table, f"{root}/lineitem.parquet", row_group_size=50_000, store_decimal_as_integer=True)

until = datetime.date(1998, 12, 1) - datetime.timedelta(days=90)  # DELTA 90, the validation value
query = {
    "paths": "lineitem.parquet",
    "filters": [["l_shipdate", "<=", until.isoformat()]],
    "group_by": ["l_returnflag", "l_linestatus"],
    "aggregates": ["sum(l_quantity)", "sum(l_extendedprice)", "sum(l_extendedprice*(1-l_discount))",
                   "sum(l_extendedprice*(1-l_discount)*(1+l_tax))", "avg(l_quantity)", "avg(l_extendedprice)",
                   "avg(l_discount)", "count"],
}
server = ScanServer(ServeConfig(host="127.0.0.1", port=0, root=root, device=True)).start_background()
try:
    req = urllib.request.Request(server.url + "/v1/query", data=json.dumps(query).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        body = json.loads(resp.read())
finally:
    server.close()
for g in body["groups"]:
    print(g["key"], json.dumps(g["aggregates"]))
print(f"{body['group_count']} groups, {body['rows_matched']} of {body['rows_scanned']} rows, {body['units']} units, "
      f"{metrics.get('query_group_units')} of them grouped in device memory")

# the same answer in Python integers over the unscaled values
keep = days <= (until - epoch).days
text = lambda unscaled, scale: str(Decimal(int(unscaled)).scaleb(-scale))  # noqa: E731
mean = lambda unscaled, count, scale: format(  # noqa: E731
    (Decimal(int(unscaled)).scaleb(-scale) / count).quantize(Decimal(1).scaleb(-scale - 4), rounding=ROUND_HALF_UP), "f")
want = []
for f, s in sorted((f, s) for f in "ANR" for s in "FO"):
    rows = np.flatnonzero(keep & (flag == "RAN".index(f)) & (status == "FO".index(s)))
    q, p, d, x = (a[rows].tolist() for a in (quantity * 100, price, discount, tax))
    want.append({"key": [f, s], "aggregates": dict(zip(query["aggregates"], [
        text(sum(q), 2), text(sum(p), 2), text(sum(pi * (100 - di) for pi, di in zip(p, d)), 4),
        text(sum(pi * (100 - di) * (100 + xi) for pi, di, xi in zip(p, d, x)), 6),
        mean(sum(q), len(rows), 2), mean(sum(p), len(rows), 2), mean(sum(d), len(rows), 2), len(rows)]))})
assert body["groups"] == want, (body["groups"], want)
print("equal to the sums in Python integers to the last digit, group by group")
