"""Corpus kind tpch_lineitem: TPC-H's LINEITEM by dbgen's rules, from a seed.

Host only (numpy + pyarrow; never jax), so the worker processes of
lib/corpus.py can load it. The table is split into `files` files by row range;
file `index` draws from (seed, index) and holds `rows_per_file` rows in
order-key order (its last order may be cut). What the TPC-H specification
fixes (clauses 1.4.1 and 4.2.3) is kept:

  orders     1-7 lines each (uniform); order keys sparse, 8 of every 32;
             O_ORDERDATE uniform in 1992-01-01 .. 1998-08-02
  keys       L_PARTKEY uniform in 1 .. `parts`; L_SUPPKEY one of the part's
             four suppliers: (p + j * (S/4 + (p - 1)/S)) mod S + 1
  amounts    L_QUANTITY 1..50, L_DISCOUNT 0.00..0.10, L_TAX 0.00..0.08 (all
             uniform); L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE with
             P_RETAILPRICE = (90000 + ((p/10) mod 20001) + 100 (p mod 1000))/100
  dates      ship = order + 1..121, commit = order + 30..90,
             receipt = ship + 1..30
  flags      L_RETURNFLAG R or A where the receipt date is on or before
             1995-06-17, else N; L_LINESTATUS O where the ship date is after
             1995-06-17, else F
  text       4 ship instructions, 7 ship modes; a comment is a substring of
             a text pool (dbgen's is 300 MB of its grammar's sentences; this
             one is 1 MiB of the same word lists, from a seed of its own)

Decimals are DECIMAL(15,2) stored as INT64 (`store_decimal_as_integer`: what
Spark and DuckDB write), dates DATE (INT32), every column required. The
writer's dictionary limit is pyarrow's 1 MiB: L_EXTENDEDPRICE has ~660,000
distinct values in a 2^20-row group, so its chunk starts as a dictionary and
falls back to PLAIN pages — a mixed chunk in every row group, as every
mainstream writer leaves it. `rehearsal` shrinks the limit and the page size
with the group, so that a rehearsal-size corpus has the same mix.

write_file returns the file's share of every seeded query's reference answer
(lib/reference_tpch.py over pyarrow's read of the file just written).
"""

from __future__ import annotations

import datetime
from pathlib import Path

EPOCH = datetime.date(1970, 1, 1)
ORDERDATE_MIN = (datetime.date(1992, 1, 1) - EPOCH).days
ORDERDATE_MAX = (datetime.date(1998, 8, 2) - EPOCH).days
CURRENTDATE = (datetime.date(1995, 6, 17) - EPOCH).days
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
DECIMALS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
COLUMNS = (
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", *DECIMALS,
    "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
    "l_shipinstruct", "l_shipmode", "l_comment",
)
COMMENT_MIN, COMMENT_MAX = 10, 43
POOL_SEED, POOL_BYTES = 19920101, 1 << 20  # the text pool is the corpus's, not the run's
WORDS = {
    "noun": "foxes ideas theodolites pinto-beans instructions dependencies excuses platelets asymptotes courts "
            "dolphins multipliers sauternes warthogs frets dinos attainments somas braids hockey-players frays "
            "warhorses dugouts notornis epitaphs pearls tithes waters orbits gifts sheaves depths sentiments "
            "decoys realms pains grouches escapades packages requests accounts deposits",
    "verb": "sleep wake are cajole haggle nag use boost affix detect integrate maintain nod was lose sublate solve "
            "thrash promise engage hinder print x-ray breach eat grow impress mold poach serve run dazzle snooze "
            "doze unwind kindle play hang believe doubt",
    "adjective": "furious sly careful blithe quick fluffy slow quiet ruthless thin close dogged daring brave stealthy "
                 "permanent enticing idle busy regular final ironic even bold silent special pending unusual express",
    "adverb": "sometimes always never furiously slyly carefully blithely quickly fluffily slowly quietly ruthlessly "
              "thinly closely doggedly daringly bravely stealthily permanently enticingly idly busily regularly "
              "finally ironically evenly boldly silently",
    "preposition": "about above according-to across after against along alongside-of among around at atop before "
                   "behind beneath beside besides between beyond by despite during except for from in-place-of inside "
                   "instead-of into near of on outside over past since through throughout to toward under until up "
                   "upon without with within",
    "end": ". ; : ? ! --",
}


def retail_price_cents(partkey):
    """P_RETAILPRICE of clause 4.2.3, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def text_pool():
    """1 MiB of sentences from the grammar's word lists: adjective noun verb
    adverb preposition adjective noun end, words drawn uniformly."""
    import numpy as np

    rng = np.random.default_rng(POOL_SEED)
    lists = {k: [w.replace("-", " ") for w in v.split()] for k, v in WORDS.items()}
    order = ("adjective", "noun", "verb", "adverb", "preposition", "adjective", "noun", "end")
    parts, size = [], 0
    while size < POOL_BYTES:
        s = " ".join(lists[k][int(rng.integers(len(lists[k])))] for k in order) + " "
        parts.append(s)
        size += len(s)
    return np.frombuffer("".join(parts).encode()[:POOL_BYTES], dtype=np.uint8)


def build_columns(spec: dict, seed: int, index: int) -> dict:
    """File `index` as numpy arrays, one per column (decimals in cents, dates
    in days since 1970-01-01; text columns as indices into their lists, the
    comment as (starts, lengths) into the text pool), from (seed, index)."""
    import numpy as np

    n = spec["rows_per_file"]
    rng = np.random.default_rng([seed, index])
    lines = rng.integers(1, 8, n // 4 + n // 16 + 64)
    while lines.sum() < n:  # never at these sizes; the law, not the luck, decides
        lines = np.concatenate([lines, rng.integers(1, 8, n // 16 + 64)])
    ends = np.cumsum(lines)
    orders = int(np.searchsorted(ends, n, side="left")) + 1
    lines, ends = lines[:orders], ends[:orders]
    order_of_row = np.repeat(np.arange(orders), lines)[:n]
    first_row = ends - lines
    # files are row ranges of one table: order numbers go on from file to file
    number = index * spec["orders_per_file"] + np.arange(orders, dtype=np.int64)
    if orders > spec["orders_per_file"]:
        raise ValueError(f"tpch_lineitem: {orders} orders in a file of {n} rows pass orders_per_file")
    orderkey = (number // 8) * 32 + number % 8 + 1
    orderdate = rng.integers(ORDERDATE_MIN, ORDERDATE_MAX + 1, orders)[order_of_row]
    partkey = rng.integers(1, spec["parts"] + 1, n)
    s = spec["suppliers"]
    quantity = rng.integers(1, 51, n)
    ship = orderdate + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    return {
        "l_orderkey": orderkey[order_of_row],
        "l_partkey": partkey,
        "l_suppkey": (partkey + rng.integers(0, 4, n) * (s // 4 + (partkey - 1) // s)) % s + 1,
        "l_linenumber": (np.arange(n) - first_row[order_of_row] + 1).astype(np.int32),
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail_price_cents(partkey),
        "l_discount": rng.integers(0, 11, n),
        "l_tax": rng.integers(0, 9, n),
        "l_returnflag": np.where(receipt <= CURRENTDATE, rng.integers(0, 2, n), 2),  # R, A | N
        "l_linestatus": (ship > CURRENTDATE).astype(np.int64),  # F | O
        "l_shipdate": ship.astype(np.int32),
        "l_commitdate": (orderdate + rng.integers(30, 91, n)).astype(np.int32),
        "l_receiptdate": receipt.astype(np.int32),
        "l_shipinstruct": rng.integers(0, len(INSTRUCTIONS), n),
        "l_shipmode": rng.integers(0, len(MODES), n),
        "l_comment": (rng.integers(0, POOL_BYTES - COMMENT_MAX, n), rng.integers(COMMENT_MIN, COMMENT_MAX + 1, n)),
    }


def _decimal(cents):
    """int64 cents as decimal128(15, 2): the low word, and its sign in the high one."""
    import numpy as np
    import pyarrow as pa

    words = np.empty((len(cents), 2), dtype=np.int64)
    words[:, 0] = cents
    words[:, 1] = cents >> 63
    return pa.Array.from_buffers(pa.decimal128(15, 2), len(cents), [None, pa.py_buffer(words)])


def _comments(pool, starts, lengths):
    """Substrings of the pool as one string array, 2^18 rows at a time."""
    import numpy as np
    import pyarrow as pa

    chunks = []
    for lo in range(0, len(starts), 1 << 18):
        st, ln = starts[lo:lo + (1 << 18)], lengths[lo:lo + (1 << 18)]
        offsets = np.zeros(len(ln) + 1, dtype=np.int32)
        np.cumsum(ln, out=offsets[1:])
        where = np.repeat(st - offsets[:-1], ln) + np.arange(offsets[-1])
        chunks.append(pa.StringArray.from_buffers(len(ln), pa.py_buffer(offsets), pa.py_buffer(pool[where])))
    return pa.chunked_array(chunks, type=pa.string())


def schema():
    import pyarrow as pa

    types = {"l_orderkey": pa.int64(), "l_partkey": pa.int64(), "l_suppkey": pa.int64(), "l_linenumber": pa.int32(),
             **{c: pa.decimal128(15, 2) for c in DECIMALS},
             **{c: pa.date32() for c in ("l_shipdate", "l_commitdate", "l_receiptdate")}}
    return pa.schema([pa.field(c, types.get(c, pa.string()), nullable=False) for c in COLUMNS])


def build_table(spec: dict, seed: int, index: int):
    """File `index` as a pyarrow table."""
    import pyarrow as pa

    cols = build_columns(spec, seed, index)
    sch = schema()
    lists = {"l_returnflag": ("R", "A", "N"), "l_linestatus": ("F", "O"),
             "l_shipinstruct": INSTRUCTIONS, "l_shipmode": MODES}
    arrays = []
    for f in sch:
        v = cols[f.name]
        if f.name in DECIMALS:
            a = _decimal(v)
        elif f.name == "l_comment":
            a = _comments(text_pool(), *v)
        elif f.name in lists:
            a = pa.array(lists[f.name]).take(pa.array(v))
        else:
            a = pa.array(v).cast(f.type)
        arrays.append(a)
    return pa.Table.from_arrays(arrays, schema=sch)


def file_name(index: int) -> str:
    return f"lineitem-{index:03d}.parquet"


def write_file(spec: dict, seed: int, index: int, directory: str, queries: list) -> dict:
    """Write one file and return what later comparisons need of it."""
    import pyarrow.parquet as pq

    from reference_tpch import file_shares  # benchmark/lib is on sys.path

    path = str(Path(directory) / file_name(index))
    table = build_table(spec, seed, index)
    pq.write_table(
        table, path, compression=spec["compression"], row_group_size=spec["row_group_rows"],
        store_decimal_as_integer=True, dictionary_pagesize_limit=spec["dictionary_pagesize_limit"],
        data_page_size=spec["data_page_size"], data_page_version=spec["data_page_version"],
    )
    return {"index": index, "rows": table.num_rows, "shares": file_shares(path, queries)}


def rehearsal(spec: dict, rows: int) -> tuple:
    """The table at `rows` rows a group, three groups a file, for a CPU
    rehearsal: (spec, scale). The dictionary limit and the page size shrink
    with the group, so that l_extendedprice is a mixed chunk there too; key
    ranges and every law stay."""
    scale = rows / spec["row_group_rows"]
    return dict(spec, row_group_rows=rows, rows_per_file=3 * rows, orders_per_file=rows,
                dictionary_pagesize_limit=max(1024, int(spec["dictionary_pagesize_limit"] * scale)),
                data_page_size=max(1024, int(spec["data_page_size"] * scale))), scale
