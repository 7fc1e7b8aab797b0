"""Traffic kind stream_reader_wide: decode-to-HBM of the record at its full
width — stream_reader's sibling for a configuration that delivers DOUBLE and
byte-array dictionary columns.

The same loop and the same closing rule as stream_reader:
`FileReader.read_row_groups_device(columns=..., doubles=config["doubles"])`,
file after file in seeded order, round and round; one `jax.block_until_ready`
per file; the arrays are dropped once counted; the window closes at the first
delivery that comes back at or after --seconds.

What differs is what a delivery holds and so what it is compared with. A
DOUBLE column arrives in the form the configuration states (`doubles`:
"float32" — numpy's astype(float32) of the file's float64, bit for bit — or
"bits", the uint64 IEEE-754 patterns), a string column as dictionary indices
plus its dictionary. The corpus facts hold sums of the integer columns only,
so the reference is pyarrow's read of the same files, reduced by
lib/reference_wide.py in worker threads while the device path warms up.

Correctness: the warm-up file is compared with pyarrow in full — every value's
bit pattern, every null position, every string, the residency and the stated
form of every array. Inside the window each delivery costs one jitted
reduction per row group, enqueued and left on the device: per column the
wrapped uint64 sum of the delivered bit patterns (for a string column, of each
row's first byte: the dictionary's first bytes gathered through the indices'
counts), beside the row and non-null counts the arrays' shapes give; fetched
and compared after the window. No def levels are uploaded for it.
"""

from __future__ import annotations

import time
from functools import partial

MASK64 = 0xFFFFFFFFFFFFFFFF


def _arrays(groups) -> list:
    return [a for g in groups for dc in g.values()
            for a in (dc.values, dc.indices, dc.dict_data, dc.dict_offsets) if a is not None]


def setup(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu import FileReader
    from reference_wide import FileDigests, patterns  # benchmark/lib is on sys.path

    columns = ctx.config["delivered_columns"]
    doubles = ctx.config["doubles"]
    files = ctx.facts["files"]
    ctx.order = [int(i) for i in np.random.default_rng([ctx.seed, 1]).permutation(len(files))]
    # the reference digests of all 12 files, by worker threads, from here on
    ctx.digests = FileDigests(ctx.facts["paths"], columns, doubles)

    def as_u64(v):
        if v.dtype == jnp.float32:
            v = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return v.astype(jnp.uint64)  # int64 -> uint64 keeps the bits

    @partial(jax.jit, static_argnames=("n_dict",))
    def digest(values, strings, n_dict):
        sums = [jnp.sum(as_u64(v), dtype=jnp.uint64) for v in values]
        for idx, data, offsets in strings:
            first = data[offsets[:-1].astype(jnp.int32)].astype(jnp.uint64)  # n_dict entries
            counts = jnp.sum(idx[:, None] == jnp.arange(n_dict, dtype=idx.dtype)[None, :],
                             axis=0, dtype=jnp.uint64)
            sums.append(jnp.sum(counts * first, dtype=jnp.uint64))
        return sums

    def deliver(index: int):
        with ctx.spans.span("read file"):
            with FileReader(ctx.facts["paths"][index]) as r:
                groups = r.read_row_groups_device(columns=columns, device=ctx.device, doubles=doubles)
        with ctx.spans.span("wait block_until_ready"):
            jax.block_until_ready(_arrays(groups))
        return groups

    def check(groups):
        """Per group: ([rows, non-null] per column, the digest left on the
        device). Numeric columns first, string columns after, each in the
        configuration's order."""
        with ctx.spans.span("verify"):
            out = []
            for g in groups:
                dcs = [g[(c,)] for c in columns]
                numeric = [dc for dc in dcs if dc.values is not None]
                strings = [dc for dc in dcs if dc.values is None]
                n_dict = {int(dc.dict_offsets.shape[0]) - 1 for dc in strings}
                if len(n_dict) > 1:
                    raise SystemExit("bench: string columns of one group differ in dictionary size")
                counts = [(dc.num_values, int((dc.values if dc.values is not None else dc.indices).shape[0]))
                          for dc in numeric + strings]
                out.append((counts, digest([dc.values for dc in numeric],
                                           [(dc.indices, dc.dict_data, dc.dict_offsets) for dc in strings],
                                           n_dict.pop() if n_dict else 0)))
            return out

    ctx.deliver, ctx.check = deliver, check

    # warm-up: the first file of the order, compared in full with pyarrow
    first = ctx.order[0]
    groups = deliver(first)
    ref = pq.read_table(ctx.facts["paths"][first], columns=columns)
    ctx.numeric = [c for c in columns if patterns(ref[c].slice(0, 1), doubles) is not None]
    ctx.strings = [c for c in columns if c not in ctx.numeric]
    off = 0
    for gi, g in enumerate(groups):
        n = g[(columns[0],)].num_values
        for c in columns:
            col = ref[c].slice(off, n).combine_chunks()
            dc = g[(c,)]
            where = f"bench: warm-up: {c} of group {gi}"
            for a in (dc.values, dc.indices, dc.dict_data, dc.dict_offsets):
                if a is not None and {d.platform for d in a.devices()} != {ctx.device.platform}:
                    raise SystemExit(f"{where} is not resident on {ctx.device.platform}")
            if not np.array_equal(np.asarray(dc.def_levels) == 1, col.is_valid().to_numpy(zero_copy_only=False)):
                raise SystemExit(f"{where}: null positions differ from pyarrow")
            want = patterns(col, doubles)
            if want is None:
                words = pa.array([bytes(w).decode() for w in dc.dictionary.to_list()])
                if dc.indices is None or not words.take(pa.array(np.asarray(dc.indices))).equals(col.drop_null()):
                    raise SystemExit(f"{where}: strings differ from pyarrow")
                continue
            got = np.asarray(dc.values)
            if pa.types.is_floating(col.type):
                form = {"float32": np.float32, "bits": np.uint64}[doubles]
                if getattr(dc, "double_form", None) != doubles or got.dtype != form:
                    raise SystemExit(f"{where}: delivered as {got.dtype}, not in the stated form {doubles!r}")
            if not np.array_equal(got.view(want.dtype), want):
                raise SystemExit(f"{where} differs from pyarrow bit for bit")
        off += n
    ctx.want = ctx.digests.result()
    if off != ref.num_rows or not _same(ctx, check(groups), ctx.want[first]):
        raise SystemExit("bench: warm-up: row count or column digests differ from the reference")
    ctx.say(f"warm-up: file {first} ({off} rows x {len(columns)} columns, doubles={doubles}) equals "
            f"pyarrow bit for bit; reference digests of {len(ctx.want)} files taken")


def _same(ctx, checked, want: dict) -> bool:
    """A file's delivery (check()'s groups) against its reference digest."""
    names = ctx.numeric + ctx.strings
    got = {c: [0, 0, 0] for c in names}
    for counts, sums in checked:
        for c, (rows, non_null), s in zip(names, counts, sums):
            got[c][0] += rows
            got[c][1] += non_null
            got[c][2] = (got[c][2] + int(s)) & MASK64
    return all(tuple(got[c]) == tuple(want[c]) for c in names)


def window(ctx, seconds: float) -> dict:
    pending = []
    k = 1
    t0 = time.perf_counter()
    while True:
        index = ctx.order[k % len(ctx.order)]
        groups = ctx.deliver(index)
        elapsed = time.perf_counter() - t0
        pending.append((index, ctx.check(groups)))
        del groups
        k += 1
        if elapsed >= seconds:
            break
    good = [i for i, d in pending if _same(ctx, d, ctx.want[i])]
    rows = sum(ctx.facts["files"][i]["rows"] for i in good)  # a wrong delivery is missing from the rate
    return {
        "attempted": len(pending), "failed": len(pending) - len(good), "rows": rows, "window_s": elapsed,
        "metrics": {"rows_per_s": rows / elapsed},
    }


def close(ctx) -> None:
    digests = getattr(ctx, "digests", None)
    if digests is not None:
        digests.close()
