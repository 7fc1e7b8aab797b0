"""One run of a packed stream cell with the timed path broken underneath, or
with a control in the program's place: `correct` has to come out false.
broken.py's sibling for cells whose entry point is
`FileReader.iter_device_batches(lists="pack")`.

    python benchmark/selftest/broken_packed.py --fault <name> -- --workload tok-8k.packed --seed <n> --seconds <s> [run.py's arguments]

Everything after `--` goes to benchmark/run.py's main, in this process; what is
planted wraps the iterator where a file's batches are produced. The first file
of the process (the warm-up file, compared batch by batch during set-up) is
left whole, so that what fails is the comparison the timed files get: the
per-file sequence count and digests against the reference, counted in `failed`.

Faults (a harness that cannot see them proves nothing by `correct: true`):
  token_shifted        two neighbouring, different tokens of one sequence of
                       every file change places: every plain sum stays, only a
                       position-sensitive one can tell
  sequence_left_out    every file lacks its last sequence
  sequences_swapped    two neighbouring sequences of one batch of every file
                       change places, whole (tokens, segment ids, positions):
                       every sum over slots stays, only one weighted by the
                       sequence's index in its file can tell
Controls (a guarantee of the configuration broken, the step a later PR could
be tempted to take):
  segments_not_marked  segment ids all 1 and positions the slot number on
                       every real token: the tokens right, the boundaries lost
                       (what concatenating and cutting alone gives)
  carry_dropped        each row group packed on its own, its tail padded
                       instead of carried into the next group: no document
                       crosses a group, and the stream is longer for it
  none                 nothing planted: the same route comes out correct

selftest/test_faults_packed.py runs each at a rehearsal size on the CPU;
PERF.md section 2 has the controls' readings on the chip at the cell's own size.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def token_shifted(reader, batches, kw):
    import jax
    import numpy as np

    done = False
    for b in batches:
        if not done:
            tokens = np.asarray(b.tokens).copy()
            j = int(np.flatnonzero(tokens[0, :-1] != tokens[0, 1:])[0])
            tokens[0, [j, j + 1]] = tokens[0, [j + 1, j]]
            b = b._replace(tokens=jax.device_put(tokens, b.tokens.sharding))
            done = True
        yield b


def sequences_swapped(reader, batches, kw):
    import jax
    import numpy as np

    done = False
    for b in batches:
        if not done and b.tokens.shape[0] > 1:
            arrays = [np.asarray(a).copy() for a in b]
            for a in arrays:
                a[[0, 1]] = a[[1, 0]]
            b = type(b)(*(jax.device_put(a, b.tokens.sharding) for a in arrays))
            done = True
        yield b


def sequence_left_out(reader, batches, kw):
    import jax
    import numpy as np

    held = None
    for b in batches:  # one batch behind, so that the last one is known
        if held is not None:
            yield held
        held = b
    if held is not None and held.tokens.shape[0] > 1:
        yield type(held)(*(jax.device_put(np.asarray(a)[:-1], a.sharding) for a in held))


def segments_not_marked(reader, batches, kw):
    import jax.numpy as jnp

    for b in batches:
        real = b.segment_ids > 0
        slots = jnp.broadcast_to(jnp.arange(b.tokens.shape[1], dtype=jnp.int32), b.tokens.shape)
        yield b._replace(segment_ids=real.astype(jnp.int32), positions=jnp.where(real, slots, 0))


def carry_dropped(reader, batches, kw):
    """The control stands in the program's place: pyarrow reads each row group
    and the reference packs it alone."""
    import jax
    import numpy as np
    import pyarrow.parquet as pq

    sys.path.insert(0, str(BENCH / "lib"))
    from reference_packed import pack

    first = next(batches)
    batches.close()
    size, seq_len = kw["batch_size"], kw["seq_len"]
    (column,) = kw["columns"]
    file = pq.ParquetFile(reader._source.path)
    parts = [pack(file.read_row_group(g, columns=[column])[column], seq_len) for g in range(file.num_row_groups)]
    whole = [np.concatenate([p[k] for p in parts]) for k in range(3)]
    for lo in range(0, len(whole[0]), size):
        yield type(first)(*(jax.device_put(a[lo:lo + size], first.tokens.sharding) for a in whole))


FAULTS = {f.__name__: f for f in (token_shifted, sequence_left_out, sequences_swapped, segments_not_marked,
                                  carry_dropped)}


def plant(fault) -> None:
    from parquet_tpu import FileReader

    whole = FileReader.iter_device_batches
    files = 0

    def broken(self, batch_size, **kw):
        nonlocal files
        batches = whole(self, batch_size, **kw)
        files += 1
        return batches if files == 1 else fault(self, batches, dict(kw, batch_size=batch_size))

    FileReader.iter_device_batches = broken


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=[*FAULTS, "none"])
    a, rest = ap.parse_known_args()
    sys.path[:0] = [str(BENCH), str(BENCH.parent)]
    import run

    if a.fault != "none":
        plant(FAULTS[a.fault])
    sys.argv = [str(BENCH / "run.py"), *(r for r in rest if r != "--")]
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
