"""Traffic kind stream_loader: host decode + upload, the control of
stream_reader.

`ParquetDataset(paths, batch_size=batch_rows, columns=delivered_columns,
nullable="zero", device=..., num_epochs=None)` over the same files in month
order (the dataset sorts its paths), epoch after epoch; the consumer takes the next batch and ends its step in
`jax.block_until_ready`. The window closes at the first batch that comes back
at or after --seconds.

Correctness: the first `warmup_batches` batches are compared with pyarrow bit
for bit (nulls as zeros). Inside the window each batch costs one jitted
reduction left on the device — one wrapped int64 sum per column — fetched and
compared after the window with the sums taken when the corpus was written.
"""

from __future__ import annotations

import time


def setup(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    from parquet_tpu import ParquetDataset

    columns = ctx.config["delivered_columns"]
    files = ctx.facts["files"]
    batch = ctx.cell["batch_rows"]
    per_sum = batch // ctx.corpus["sum_rows"]
    if per_sum * ctx.corpus["sum_rows"] != batch or files[0]["rows"] % batch:
        raise SystemExit("bench: batch_rows must be a multiple of the corpus's sum_rows and divide a file")
    order = list(range(len(files)))  # the dataset sorts its paths: month order, epoch after epoch
    # wrapped sums per batch, in delivery order over one epoch
    ctx.want = np.concatenate([
        np.stack([np.asarray(files[i]["sums"][c], dtype=np.int64).reshape(-1, per_sum).sum(axis=1, dtype=np.int64)
                  for c in columns], axis=1)
        for i in order])
    ctx.batch = batch
    ctx.dataset = ParquetDataset(
        [ctx.facts["paths"][i] for i in order], batch_size=batch, columns=columns,
        nullable="zero", device=ctx.device, num_epochs=None,
    )
    ctx.batches = iter(ctx.dataset)

    @jax.jit
    def digest(b):
        return jnp.stack([jnp.sum(b[(c,)], dtype=jnp.int64) for c in columns])

    ctx.digest = digest

    ref = pq.read_table(ctx.facts["paths"][order[0]], columns=columns)
    for k in range(ctx.cell["warmup_batches"]):
        b = next(ctx.batches)
        jax.block_until_ready(b)
        for c in columns:
            col = ref[c].slice(k * batch, batch).combine_chunks()
            if col.type != "int64":
                col = col.cast("int64")
            if not np.array_equal(np.asarray(b[(c,)]), pc.fill_null(col, 0).to_numpy()):
                raise SystemExit(f"bench: warm-up: batch {k}, {c} differs from pyarrow")
            if {d.platform for d in b[(c,)].devices()} != {ctx.device.platform}:
                raise SystemExit(f"bench: warm-up: {c} is not resident on {ctx.device.platform}")
        if not np.array_equal(np.asarray(ctx.digest(b)), ctx.want[k]):
            raise SystemExit(f"bench: warm-up: batch {k}: column sums differ from the corpus facts")
    ctx.delivered = ctx.cell["warmup_batches"]
    ctx.say(f"warm-up: {ctx.delivered} batches of {batch} rows x {len(columns)} columns equal pyarrow bit for bit")


def window(ctx, seconds: float) -> dict:
    import jax
    import numpy as np

    pending = []
    first = ctx.delivered
    t0 = time.perf_counter()
    while True:
        with ctx.spans.span("wait next batch"):
            b = next(ctx.batches)
        with ctx.spans.span("wait block_until_ready"):
            jax.block_until_ready(b)
        elapsed = time.perf_counter() - t0
        pending.append(ctx.digest(b))
        del b
        if elapsed >= seconds:
            break
    got = np.stack([np.asarray(d) for d in pending])
    want = ctx.want[(first + np.arange(len(pending))) % len(ctx.want)]
    good = int((got == want).all(axis=1).sum())
    ctx.delivered += len(pending)
    return {
        "attempted": len(pending), "failed": len(pending) - good, "rows": good * ctx.batch,
        "window_s": elapsed, "metrics": {"rows_per_s": good * ctx.batch / elapsed},
    }


def close(ctx) -> None:
    batches = getattr(ctx, "batches", None)
    if batches is not None:
        batches.close()
