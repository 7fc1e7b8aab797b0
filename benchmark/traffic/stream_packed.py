"""Traffic kind stream_packed: a tokenised corpus into HBM as packed sequences,
the input stage of a pre-training job on one data-parallel chip.

One consumer, closed loop. Files in seeded order, round and round; per file

    FileReader.iter_device_batches(batch_sequences, columns=columns, lists="pack",
                                   seq_len=seq_len, drop_remainder=drop_remainder, device=...)

and per batch one jitted digest added to the file's running one on the device
(a training step's stand-in: it reads all three arrays); one
`jax.block_until_ready` per file; the batches are dropped once digested. The window closes at the first file that ends at or
after --seconds; `rows` = the documents of every correct file.

Correctness, every limit 0. Warm-up: the first `warmup_files` files of the
order, every batch compared with the reference in full — values of tokens,
segment ids and positions, residency, dtype, and both batch shapes (whole
batches, and the file's short last one). In the window, per file: the sequence
count, and seven wrapped uint64 sums — the sums of tokens, of tokens x (slot +
1), of segment ids x (slot + 1) and of positions, and of each of the three
arrays x (the sequence's index in its file + 1), so that neither a value moved
within its sequence nor a sequence or batch delivered out of its place passes
— against the reference's (lib/reference_packed.py: pack + digests
of pyarrow's read of the same file, taken during set-up by the corpus's worker
processes as each file is written, corpora/token_docs.py; neither imports the
program). A file's short last batch has another shape for
every remainder, so its digest is taken on the host from the fetched arrays:
a jitted digest would compile once a remainder, inside the window.
"""

from __future__ import annotations

import time

MASK64 = 0xFFFFFFFFFFFFFFFF
SUMS = ("tokens", "tokens_weighted", "segments_weighted", "positions",
        "tokens_by_sequence", "segments_by_sequence", "positions_by_sequence")


def file_reference(path: str, column: str, seq_len: int) -> dict:
    import pyarrow.parquet as pq

    from reference_packed import digests, pack  # benchmark/lib is on sys.path

    return digests(*pack(pq.read_table(path, columns=[column])[column], seq_len))


def setup(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow.parquet as pq

    from parquet_tpu import FileReader
    from reference_packed import digests, pack

    (column,) = ctx.cell["columns"]
    seq_len, batch = ctx.cell["seq_len"], ctx.cell["batch_sequences"]
    files, paths = ctx.facts["files"], ctx.facts["paths"]
    ctx.order = [int(i) for i in np.random.default_rng([ctx.seed, 1]).permutation(len(files))]
    # the reference digests of every file: the corpus's, where it packed at this cell's length
    ctx.want = {f["index"]: f["digests"] if f["seq_len"] == seq_len else file_reference(paths[f["index"]], column, seq_len)
                for f in files}

    @jax.jit
    def digest(b, so_far, first_sequence):
        by_slot = jnp.arange(1, seq_len + 1, dtype=jnp.uint64)
        by_sequence = first_sequence.astype(jnp.uint64) + jnp.arange(1, batch + 1, dtype=jnp.uint64)
        wide = [a.astype(jnp.int64).astype(jnp.uint64) for a in b]  # an int32's sign extends
        slots = [jnp.sum(a, axis=0) for a in wide]
        rows = [jnp.sum(a, axis=1) for a in wide]
        return so_far + jnp.stack(
            [jnp.sum(slots[0]), jnp.sum(slots[0] * by_slot), jnp.sum(slots[1] * by_slot), jnp.sum(slots[2]),
             *(jnp.sum(r * by_sequence) for r in rows)])

    nothing = jax.device_put(np.zeros(len(SUMS), dtype=np.uint64), ctx.device)

    def deliver(index: int, inspect=None):
        """One file: ([sequences per batch], the whole batches' digest on the
        device, the short last batch or None). `inspect(k, batch)` sees every
        batch first."""
        rows, sums, short = [], nothing, None
        with ctx.spans.span("read file"):
            with FileReader(paths[index]) as r:
                for k, b in enumerate(r.iter_device_batches(
                        batch, columns=[column], lists="pack", seq_len=seq_len,
                        drop_remainder=ctx.cell["drop_remainder"], device=ctx.device)):
                    if inspect is not None:
                        inspect(k, b)
                    rows.append(int(b.tokens.shape[0]))
                    if rows[-1] == batch:
                        sums = digest(b, sums, np.int32(sum(rows[:-1])))
                    else:
                        short = b
        with ctx.spans.span("wait block_until_ready"):
            jax.block_until_ready((sums, short))
        return rows, sums, short

    def check(delivery) -> dict:
        """A delivery as the reference's digests: the device's sums fetched,
        the short batch digested on the host."""
        rows, sums, short = delivery
        got = dict(zip(SUMS, np.asarray(sums).tolist()))
        if short is not None:
            for name, v in digests(*(np.asarray(a) for a in short), first_sequence=sum(rows[:-1])).items():
                if name in got:
                    got[name] = (got[name] + v) & MASK64
        return dict(got, sequences=sum(rows))

    ctx.deliver, ctx.check = deliver, check

    # warm-up: the first files of the order, every batch compared with the reference in full
    for index in ctx.order[: ctx.cell["warmup_files"]]:
        want = pack(pq.read_table(paths[index], columns=[column])[column], seq_len)
        n_seq = want[0].shape[0]

        def inspect(k, b, want=want, n_seq=n_seq, index=index):
            where = f"bench: warm-up: file {index}, batch {k}"
            rows = min(batch, n_seq - k * batch)
            for name, a, w in zip(b._fields, b, want):
                if {d.platform for d in a.devices()} != {ctx.device.platform}:
                    raise SystemExit(f"{where}: {name} is not resident on {ctx.device.platform}")
                if a.dtype != jnp.int32 or a.shape != (rows, seq_len):
                    raise SystemExit(f"{where}: {name} is {a.dtype}{list(a.shape)}, not int32[{rows}, {seq_len}]")
                if not np.array_equal(np.asarray(a), w[k * batch : k * batch + rows]):
                    raise SystemExit(f"{where}: {name} differs from the reference")

        delivery = deliver(index, inspect)
        expected = n_seq if not ctx.cell["drop_remainder"] else n_seq // batch * batch
        if sum(delivery[0]) != expected or (not ctx.cell["drop_remainder"] and check(delivery) != digests(*want)):
            raise SystemExit(f"bench: warm-up: file {index}: sequence count or digests differ from the reference")
        ctx.say(f"warm-up: file {index}: {len(delivery[0])} batches ({sum(delivery[0])} sequences of {seq_len}, "
                f"shapes [{batch}, {seq_len}] and [{delivery[0][-1]}, {seq_len}]) equal the reference bit for bit")
    ctx.say(f"reference digests of {len(ctx.want)} files ({sum(w['sequences'] for w in ctx.want.values())} sequences)")


def window(ctx, seconds: float) -> dict:
    pending = []
    k = ctx.cell["warmup_files"]
    t0 = time.perf_counter()
    while True:
        index = ctx.order[k % len(ctx.order)]
        delivery = ctx.deliver(index)
        elapsed = time.perf_counter() - t0
        pending.append((index, delivery))
        k += 1
        if elapsed >= seconds:
            break
    with ctx.spans.span("verify"):
        good = [i for i, d in pending if ctx.check(d) == ctx.want[i]]
    rows = sum(ctx.facts["files"][i]["rows"] for i in good)  # a wrong file is missing from the rate
    return {
        "attempted": len(pending), "failed": len(pending) - len(good), "rows": rows, "window_s": elapsed,
        "metrics": {"rows_per_s": rows / elapsed},
    }


def close(ctx) -> None:
    pass
