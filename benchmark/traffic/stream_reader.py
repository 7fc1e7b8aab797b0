"""Traffic kind stream_reader: decode-to-HBM by the device kernels.

`FileReader.read_row_groups_device(columns=...)`, file after file in seeded
order, round and round; one `jax.block_until_ready` per file; the arrays are
dropped once counted, as a training step drops its batch. The window closes
at the first delivery that comes back at or after --seconds, so the rate is
all the rows over all the time, with no file cut in half.

Entry point: read_row_groups_device rather than iter_device_batches — it is
the call chip_smoke.py and bench.py's headline drive, and it delivers a file's
row groups as they are (dense non-null values + definition levels), with no
re-batching between the kernels and the consumer.

Correctness: the warm-up file is compared with pyarrow bit for bit (values
and null positions). Inside the window each delivery costs one jitted
reduction, enqueued and left on the device: per column the wrapped int64 sum
of the values and the count of non-nulls, fetched and compared after the
window with the sums taken when the corpus was written.
"""

from __future__ import annotations

import time


def _arrays(groups) -> list:
    return [a for g in groups for dc in g.values() for a in (dc.values, dc.def_levels) if a is not None]


def setup(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow.parquet as pq

    from parquet_tpu import FileReader

    columns = ctx.config["delivered_columns"]
    files = ctx.facts["files"]
    ctx.order = [int(i) for i in np.random.default_rng([ctx.seed, 1]).permutation(len(files))]
    want = {}
    for f in files:
        want[f["index"]] = [
            (int(np.asarray(f["sums"][c], dtype=np.int64).sum(dtype=np.int64)), f["rows"] - f["nulls"][c])
            for c in columns
        ]
    ctx.want = want

    @jax.jit
    def digest(cols):
        return [(jnp.sum(v, dtype=jnp.int64), jnp.sum(d.astype(jnp.int32))) for v, d in cols]

    def deliver(index: int):
        with ctx.spans.span("read file"):
            with FileReader(ctx.facts["paths"][index]) as r:
                groups = r.read_row_groups_device(columns=columns, device=ctx.device)
        with ctx.spans.span("wait block_until_ready"):
            jax.block_until_ready(_arrays(groups))
        return groups

    def check(groups):
        with ctx.spans.span("verify"):
            return [digest([(g[(c,)].values, g[(c,)].def_levels) for c in columns]) for g in groups]

    ctx.deliver, ctx.check = deliver, check

    # warm-up: the first file of the order, compared in full with pyarrow
    first = ctx.order[0]
    groups = deliver(first)
    ref = pq.read_table(ctx.facts["paths"][first], columns=columns)
    off = 0
    for gi, g in enumerate(groups):
        n = g[(columns[0],)].num_values
        for c in columns:
            col = ref[c].slice(off, n).combine_chunks()
            if col.type != "int64":
                col = col.cast("int64")
            dc = g[(c,)]
            if not (np.array_equal(np.asarray(dc.values), col.drop_null().to_numpy())
                    and np.array_equal(np.asarray(dc.def_levels) == 1, col.is_valid().to_numpy(zero_copy_only=False))):
                raise SystemExit(f"bench: warm-up: {c} of group {gi} differs from pyarrow")
            if {d.platform for d in dc.values.devices()} != {ctx.device.platform}:
                raise SystemExit(f"bench: warm-up: {c} is not resident on {ctx.device.platform}")
        off += n
    if off != ref.num_rows or not _same(check(groups), want[first]):
        raise SystemExit("bench: warm-up: row count or column sums differ from the corpus facts")
    ctx.say(f"warm-up: file {first} ({off} rows x {len(columns)} columns) equals pyarrow bit for bit")


def _same(digests, want) -> bool:
    got = [[0, 0] for _ in want]
    for group in digests:
        for k, (s, n) in enumerate(group):
            got[k][0] = (got[k][0] + int(s)) & 0xFFFFFFFFFFFFFFFF
            got[k][1] += int(n)
    return all(g[0] == w[0] & 0xFFFFFFFFFFFFFFFF and g[1] == w[1] for g, w in zip(got, want))


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
    good = [i for i, d in pending if _same(d, ctx.want[i])]
    rows = sum(ctx.facts["files"][i]["rows"] for i in good)  # a wrong delivery is missing from the rate
    return {
        "attempted": len(pending), "failed": len(pending) - len(good), "rows": rows, "window_s": elapsed,
        "metrics": {"rows_per_s": rows / elapsed},
    }


def close(ctx) -> None:
    pass
