"""TPC-H Q6, the Forecasting Revenue Change Query, as one POST /v1/query call:
DATE bounds as ISO strings, DECIMAL bounds as numeric strings, and an
expression aggregate — sum(l_extendedprice*l_discount) — answered in exact
decimal arithmetic (Arrow's rules: decimal128(15,2) x decimal128(15,2) is
decimal128(31,4), its sum decimal128(38,4)). With ServeConfig(device=...)
every row group is decoded, masked and reduced in device memory, in integers
proved inside int64 from the chunks' own statistics; the answer is the same
byte for byte with or without a device."""

import sys as _sys
from pathlib import Path as _Path

_sys.path.insert(0, str(_Path(__file__).resolve().parent.parent))

import datetime
import json
import tempfile
import urllib.request
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from parquet_tpu.serve import ScanServer, ServeConfig

rng = np.random.default_rng(6)
n = 200_000
epoch = datetime.date(1970, 1, 1)
days = rng.integers((datetime.date(1992, 1, 2) - epoch).days, (datetime.date(1998, 12, 1) - epoch).days, n)
quantity = rng.integers(1, 51, n)
cents = lambda v: pa.array([Decimal(int(x)).scaleb(-2) for x in v], type=pa.decimal128(15, 2))  # noqa: E731
table = pa.table({
    "l_shipdate": pa.array(days.astype(np.int32)).cast(pa.date32()),
    "l_quantity": cents(quantity * 100),
    "l_extendedprice": cents(quantity * rng.integers(90_000, 200_000, n)),
    "l_discount": cents(rng.integers(0, 11, n)),
})
root = tempfile.mkdtemp()
# decimals as INT64, as Spark and DuckDB write them: the device lane's form
pq.write_table(table, f"{root}/lineitem.parquet", row_group_size=50_000, store_decimal_as_integer=True)

query = {
    "paths": "lineitem.parquet",
    "filters": [["l_shipdate", ">=", "1994-01-01"], ["l_shipdate", "<", "1995-01-01"],
                ["l_discount", ">=", "0.05"], ["l_discount", "<=", "0.07"], ["l_quantity", "<", "24"]],
    "aggregates": ["count", "sum(l_extendedprice*l_discount)"],
}
server = ScanServer(ServeConfig(host="127.0.0.1", port=0, root=root, device=True)).start_background()
try:
    req = urllib.request.Request(server.url + "/v1/query", data=json.dumps(query).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        body = json.loads(resp.read())
finally:
    server.close()
print(json.dumps(body["result"]), f"({body['rows_matched']} of {body['rows_scanned']} rows, {body['units']} units)")

dec = lambda s: pa.scalar(Decimal(s), type=pa.decimal128(15, 2))  # noqa: E731
keep = table.filter(pc.and_(
    pc.and_(pc.greater_equal(table["l_shipdate"], datetime.date(1994, 1, 1)), pc.less(table["l_shipdate"], datetime.date(1995, 1, 1))),
    pc.and_(pc.and_(pc.greater_equal(table["l_discount"], dec("0.05")), pc.less_equal(table["l_discount"], dec("0.07"))),
            pc.less(table["l_quantity"], dec("24.00")))))
revenue = pc.sum(pc.multiply(keep["l_extendedprice"], keep["l_discount"])).as_py()
assert body["result"] == {"count": keep.num_rows, "sum(l_extendedprice*l_discount)": str(revenue)}, (body, revenue)
print("equal to pyarrow's decimal arithmetic to the last digit:", revenue)
