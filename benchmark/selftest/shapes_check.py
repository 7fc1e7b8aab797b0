"""The compiled shapes of a stream cell, counted on the host: are they fixed by
the configuration, or do they follow the seed and the month?

    python benchmark/selftest/shapes_check.py [--config tlc-year-wide] [--seeds 7,11,3000000019]
                                              [--files 12] [--workers 6]

Host only: the configuration's corpus kind (benchmark/corpora/) and the program's prepare phase
(kernels/pipeline.py prepare_chunk_plan: page walk, freeze, no dispatch, no
device program). For every seed, file, row group and delivered column it takes
the static part of what the chunk would dispatch — everything a jitted kernel's
compile key is made of:

    hybrid  (index width, n_pad, run_pad, w_pad) per expand_hybrid_device call,
            and the dtype and length of the dictionary dict_gather_device takes
    delta   (nbits, n_pad, m_pad, p_pad, len(meta32), len(wide))
    plain   (dtype, length) of a raw upload (narrowed on the device under
            doubles="float32": one program a length bucket)

and prints each column's distinct shapes with the number of chunks that had
them. The harness warms up ONE file and fails a window that compiles, so a
cell is safe only if every column has one shape over all months and seeds:
exits 1 if a column has two, or if two seeds differ. Files are written one at
a time into a scratch directory under benchmark/.cache/, a directory a seed
(two seeds' files of one month have one name), and deleted.
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


def shapes_of_file(spec: dict, columns: list, doubles, seed: int, index: int, scratch: str) -> list:
    """[(column, shape)] over the row groups of month `index` of `seed`."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # prepare runs no device program
    from byname import load_by_name

    from parquet_tpu import FileReader
    from parquet_tpu.core.chunk import ChunkWindow, chunk_byte_range
    from parquet_tpu.kernels.pipeline import prepare_chunk_plan

    kind = load_by_name("corpora", spec["kind"])
    scratch = os.path.join(scratch, str(seed))
    os.makedirs(scratch, exist_ok=True)
    kind.write_file(dict(spec, sum_columns=[]), seed, index, scratch, [])
    path = os.path.join(scratch, kind.file_name(index))
    out = []
    try:
        with FileReader(path) as r:
            for g in range(r.num_row_groups):
                for p, cc, column in r._selected_chunks(g, columns):
                    offset, total = chunk_byte_range(cc)
                    kw = {"doubles": doubles} if doubles else {}  # a program before PR 28 has no such argument
                    plan = prepare_chunk_plan(ChunkWindow(r._fetch_chunk(offset, total), offset), cc, column, **kw)
                    shape = []
                    for f in plan.frozen_hybrid:
                        shape.append(("hybrid", f.width, f.n_pad, f.run_pad, len(f.buf) - 4 * f.run_pad))
                    up = getattr(plan, "dict_upload", None)
                    d = up if up is not None else plan.dictionary
                    if plan.frozen_hybrid and hasattr(d, "dtype") and d.ndim == 1:
                        shape.append(("dictionary", str(d.dtype), len(d)))
                    for f in plan.frozen_delta:
                        shape.append(("delta", f.nbits, f.n_pad, f.m_pad, f.p_pad, len(f.meta32), len(f.wide)))
                    if plan.plain_host is not None:
                        shape.append(("plain", str(plan.plain_host.dtype), len(plan.plain_host)))
                    if plan.host_pages:
                        shape.append(("host_decoded_pages", plan.host_pages))
                    out.append((".".join(p), tuple(shape)))
    finally:
        os.remove(path)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="tlc-year-wide")
    ap.add_argument("--seeds", default="7,11,3000000019")
    ap.add_argument("--files", type=int)
    ap.add_argument("--workers", type=int, default=min(6, os.cpu_count() or 1))
    a = ap.parse_args()
    config = json.loads((ROOT / "benchmark" / "configs" / f"{a.config}.json").read_text())
    spec, columns, doubles = config["corpus"], config["delivered_columns"], config.get("doubles")
    seeds = [int(s) for s in a.seeds.split(",")]
    files = a.files or spec["files"]
    cache = ROOT / "benchmark" / ".cache"
    cache.mkdir(exist_ok=True)
    per_seed: dict = {}
    with tempfile.TemporaryDirectory(dir=cache, prefix="shapes-") as scratch:
        with ProcessPoolExecutor(a.workers, mp_context=get_context("spawn")) as pool:
            jobs = [(s, pool.submit(shapes_of_file, spec, columns, doubles, s, i, scratch))
                    for s in seeds for i in range(files)]
            for s, job in jobs:
                per_seed.setdefault(s, Counter()).update(job.result())
    bad = 0
    for c in columns:
        shapes = Counter()
        for s in seeds:
            shapes.update({sh: n for (col, sh), n in per_seed[s].items() if col == c})
        verdict = "ok" if len(shapes) == 1 else f"{len(shapes)} SHAPES"
        print(f"{c:<24}{verdict}")
        for sh, n in shapes.most_common():
            print(f"    {n:>4} chunks  {sh}")
        bad += len(shapes) != 1
    sets = {s: frozenset(per_seed[s]) for s in seeds}
    differ = len(set(sets.values())) > 1
    programs = {sh for s in seeds for (_c, shape) in sets[s] for sh in shape}
    print(f"shapes_check: {a.config}, seeds {seeds}, {files} files x {len(columns)} columns: "
          f"{len(programs)} distinct program shapes; "
          f"{'every seed gives the same set' if not differ else 'THE SEEDS DIFFER'}; "
          f"{bad} column(s) with more than one shape")
    return 1 if bad or differ else 0


if __name__ == "__main__":
    sys.exit(main())
