"""The compiled shapes of a packed stream cell, counted on the host: are they
fixed by the configuration, or do they follow the seed, the file and the row
group? shapes_check.py's sibling for a configuration delivered through
iter_device_batches(lists="pack"), whose chunks take the padded delivery.

    python benchmark/selftest/shapes_check_packed.py [--config token-corpus-8k] [--cell tok-8k.packed]
                                                     [--seeds 7,11,3000000019] [--files 12] [--workers 6]

Host only: the configuration's corpus kind and the program's prepare phase
(kernels/pipeline.py prepare_chunk_plan(list_lengths=True): page walk, freeze,
per-document lengths; no dispatch, no device program). For every seed, file and
row group it takes the static part of what the chunk would dispatch and what
the packer would launch for it — everything a compile key is made of:

    hybrid      (index width, n_pad, run_pad, w_pad) of expand_hybrid_device; its
                n_pad output goes on whole to the index widening, the gather and
                the packer (no exact-length slice)
    dictionary  (dtype, padded length) dict_gather_device takes
    delta/plain the same for a chunk written without a dictionary
    lengths     the padded length of the per-document lengths upload
    pack        (batch x seq_len, n_pad, lengths pad) of pack_append_device; the
                emit and carry programs follow from the first two

and prints the distinct shapes with the number of chunks that had them, beside
the ranges of what IS data (tokens, documents, dictionary entries, runs a
group). The harness warms up one file and fails a window that compiles, so the
cell is safe only if there is ONE shape over all groups, files and seeds: exits
1 otherwise. Files are written one at a time into a scratch directory under
benchmark/.cache/, a directory a seed, and deleted.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from collections import Counter
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "benchmark" / "lib"), str(ROOT)]


def shapes_of_file(spec: dict, column: str, span: int, seed: int, index: int, scratch: str) -> list:
    """[(shape, facts)] over the row groups of file `index` of `seed`."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # prepare runs no device program
    from byname import load_by_name

    from parquet_tpu import FileReader
    from parquet_tpu.core.chunk import ChunkWindow, chunk_byte_range
    from parquet_tpu.kernels.device_ops import _bucket
    from parquet_tpu.kernels.pipeline import _LENGTHS_FLOOR, prepare_chunk_plan

    kind = load_by_name("corpora", spec["kind"])
    scratch = os.path.join(scratch, str(seed))
    os.makedirs(scratch, exist_ok=True)
    kind.write_file(spec, seed, index, scratch, [])
    path = os.path.join(scratch, kind.file_name(index))
    out = []
    try:
        with FileReader(path) as r:
            for g in range(r.num_row_groups):
                for _p, cc, leaf in r._selected_chunks(g, [column]):
                    offset, total = chunk_byte_range(cc)
                    plan = prepare_chunk_plan(ChunkWindow(r._fetch_chunk(offset, total), offset), cc, leaf,
                                              list_lengths=True)
                    shape, n_pad, runs = [], None, 0
                    for f in plan.frozen_hybrid:
                        shape.append(("hybrid", f.width, f.n_pad, f.run_pad, len(f.buf) - 4 * f.run_pad))
                        n_pad = f.n_pad
                        runs += int((f.buf[f.run_pad:2 * f.run_pad].view("int32") <= f.n_pad).sum())
                    d = plan.dictionary
                    if plan.frozen_hybrid and hasattr(d, "dtype") and d.ndim == 1:
                        shape.append(("dictionary", str(d.dtype), _bucket(max(len(d), 1), 1024)))
                    for f in plan.frozen_delta:
                        shape.append(("delta", f.nbits, f.n_pad, f.m_pad, f.p_pad, len(f.meta32), len(f.wide)))
                        n_pad = f.n_pad
                    if plan.plain_host is not None:
                        n_pad = _bucket(max(len(plan.plain_host), 1), 1024)
                        shape.append(("plain", str(plan.plain_host.dtype), n_pad))
                    d_pad = _bucket(max(len(plan.list_lengths), 1), _LENGTHS_FLOOR)
                    shape += [("lengths", d_pad), ("pack", span, n_pad, d_pad)]
                    if plan.host_pages or len(plan.frozen_hybrid) + len(plan.frozen_delta) > 1:
                        shape.append(("exact_delivery", plan.host_pages))  # a program a count: never safe
                    facts = (plan.list_elements, len(plan.list_lengths), len(d) if d is not None else 0, runs)
                    out.append((tuple(shape), facts))
    finally:
        os.remove(path)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="token-corpus-8k")
    ap.add_argument("--cell", default="tok-8k.packed")
    ap.add_argument("--seeds", default="7,11,3000000019")
    ap.add_argument("--files", type=int)
    ap.add_argument("--workers", type=int, default=min(6, os.cpu_count() or 1))
    a = ap.parse_args()
    config = json.loads((ROOT / "benchmark" / "configs" / f"{a.config}.json").read_text())
    cell = json.loads((ROOT / "benchmark" / "workloads" / f"{a.cell}.json").read_text())
    spec, (column,) = config["corpus"], cell["columns"]
    span = cell["batch_sequences"] * cell["seq_len"]
    seeds = [int(s) for s in a.seeds.split(",")]
    files = a.files or spec["files"]
    cache = ROOT / "benchmark" / ".cache"
    cache.mkdir(exist_ok=True)
    per_seed: dict = {}
    facts: list = []
    with tempfile.TemporaryDirectory(dir=cache, prefix="shapes-") as scratch:
        with ProcessPoolExecutor(a.workers, mp_context=get_context("spawn")) as pool:
            jobs = [(s, pool.submit(shapes_of_file, spec, column, span, s, i, scratch))
                    for s in seeds for i in range(files)]
            for s, job in jobs:
                for shape, f in job.result():
                    per_seed.setdefault(s, Counter())[shape] += 1
                    facts.append(f)
    shapes = sum(per_seed.values(), Counter())
    for shape, n in shapes.most_common():
        print(f"{n:>5} chunks")
        for part in shape:
            print(f"          {part}")
    for name, column_of in zip(("tokens", "documents", "dictionary entries", "runs"), zip(*facts)):
        print(f"{name + ' a group':<28}{min(column_of):>9} .. {max(column_of):<9} ({len(set(column_of))} distinct values)")
    differ = len({frozenset(c) for c in per_seed.values()}) > 1
    print(f"shapes_check_packed: {a.config} / {a.cell}, seeds {seeds}, {files} files: {len(shapes)} distinct shape(s) "
          f"over {sum(shapes.values())} chunks; "
          f"{'every seed gives the same set' if not differ else 'THE SEEDS DIFFER'}")
    return 1 if len(shapes) != 1 or differ else 0


if __name__ == "__main__":
    sys.exit(main())
