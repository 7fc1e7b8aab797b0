"""Micro-run for the wide record's new device work, on the chip (PR 28).

    chiprun -- python benchmark/selftest/micro_doubles.py [--seed 7]
    JAX_PLATFORMS=cpu python benchmark/selftest/micro_doubles.py --platform cpu --rows 65536   # rehearsal

1. double_narrow_device against numpy's astype(float32) on the adversarial
   families of parquet_tpu.testing.doubles (ties, subnormals, overflow, +-0,
   inf, NaN, random bit patterns), each padded to n = 2^20; then its time over
   20 back-to-back calls and its share of the HBM roofline:
   lib/kernel_bytes.py double_narrow_bytes(n) / seconds / peaks_for(kind)["hbm_bytes_per_s"].
2. dict_gather_device at 2^20 random indices: 32-bit and 64-bit entries at
   the cell's dictionary sizes (1, 2, 4, 4096, 16384) and the int64 tables of
   the 8-column cell (3, 7, 265).
3. One month of the corpus (corpora/tlc_yellow_2023.py, --seed), column by column: prepare
   on the host, then dispatch + deliver + block_until_ready of the column's
   three row groups, timed; doubles="float32". Says which of the 19 streams
   cost what, which the traced cell's per-scope totals cannot.
4. The same month whole under doubles="bits" (the form the cell does not
   run): all 19 columns through read_row_groups_device against pyarrow, every
   value and string.

Prints one JSON object and writes it to chiprun_out/micro_doubles.json.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "benchmark" / "lib"), str(ROOT)]


def timed(fn, reps: int = 20) -> float:
    """Milliseconds a call, over `reps` back-to-back calls after one warm-up."""
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = [fn() for _ in range(reps)]
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / reps


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rows", type=int, default=1 << 20)
    a = ap.parse_args()

    import numpy as np

    import parquet_tpu.kernels.device_ops as dops
    import jax
    import jax.numpy as jnp
    from parquet_tpu.testing.doubles import adversarial_doubles, same_double_form

    facts = dops.device_facts()
    if facts["platform"] != a.platform:
        print(f"micro: needs {a.platform}, jax found {facts['platform']}", file=sys.stderr)
        return 2
    n = a.rows
    out: dict = {"device": facts, "n": n}

    # 1. the narrowing kernel
    families = adversarial_doubles(a.seed, 1 << 14)
    wrong = {}
    for name, bits in families.items():
        bits = np.resize(bits, n)
        got = np.asarray(dops.double_narrow_device(jnp.asarray(bits)))
        if not same_double_form(got.view(np.float32), bits.view(np.float64), "float32"):
            with np.errstate(all="ignore"):
                want = bits.view(np.float64).astype(np.float32).view(np.uint32)
            wrong[name] = int((got != want).sum())
    x = jnp.asarray(np.resize(families["random_values"], n))
    ms = timed(lambda: dops.double_narrow_device(x))
    narrow = {"families": sorted(families), "wrong": wrong, "equals_numpy": not wrong, "ms": ms}
    if a.platform == "tpu":
        from kernel_bytes import double_narrow_bytes
        from peaks import peaks_for

        narrow["roofline_percent"] = (
            100.0 * double_narrow_bytes(n) / (ms / 1e3) / peaks_for(facts["kind"])["hbm_bytes_per_s"])
    out["double_narrow"] = narrow

    # 2. the gathers
    rng = np.random.default_rng(a.seed)
    gathers = {}
    for dt, sizes in ((np.uint32, (1, 2, 4, 4096, 16384)), (np.uint64, (1, 2, 4, 4096, 16384)),
                      (np.int64, (3, 7, 265))):
        for size in sizes:
            table = jnp.asarray(rng.integers(0, 1 << 31, size).astype(dt))
            idx = jnp.asarray(rng.integers(0, size, n).astype(np.int32))
            gathers[f"{np.dtype(dt).name}[{size}]"] = timed(lambda t=table, i=idx: dops.dict_gather_device(t, i))
    out["dict_gather_ms"] = gathers

    # 3. one month, column by column
    from byname import load_by_name

    from parquet_tpu import FileReader
    from parquet_tpu.core.chunk import ChunkWindow, chunk_byte_range
    from parquet_tpu.kernels.pipeline import prepare_chunk_plan

    config = json.loads((ROOT / "benchmark" / "configs" / "tlc-year-wide.json").read_text())
    kind = load_by_name("corpora", config["corpus"]["kind"])
    COLUMNS, file_name, write_file = kind.COLUMNS, kind.file_name, kind.write_file
    spec = dict(config["corpus"], sum_columns=[])
    if n != spec["row_group_rows"]:
        spec, _ = kind.rehearsal(spec, n)
    columns = {}
    with tempfile.TemporaryDirectory() as d:
        write_file(spec, a.seed, 0, d, [])
        with FileReader(str(Path(d) / file_name(0))) as r:
            for c in COLUMNS:
                def plans():
                    made = []
                    for g in range(r.num_row_groups):
                        ((_p, cc, column),) = r._selected_chunks(g, [c])
                        offset, total = chunk_byte_range(cc)
                        made.append(prepare_chunk_plan(ChunkWindow(r._fetch_chunk(offset, total), offset), cc, column,
                                                       doubles=config["doubles"]))
                    return made

                def run(made):
                    dcs = [p.dispatch_device().device_column() for p in made]
                    jax.block_until_ready([a_ for dc in dcs for a_ in (dc.values, dc.indices) if a_ is not None])

                run(plans())  # compile
                best = []
                for _ in range(3):
                    made = plans()
                    t0 = time.perf_counter()
                    run(made)
                    best.append((time.perf_counter() - t0) * 1e3 / len(made))
                columns[c] = min(best)
            # 4. the same month whole, under doubles="bits", against pyarrow in full
            import pyarrow.parquet as pq
            from reference_wide import patterns

            groups = r.read_row_groups_device(columns=list(COLUMNS), doubles="bits")
            ref, off, differ = pq.read_table(str(Path(d) / file_name(0))), 0, []
            for g in groups:
                rows = g[(COLUMNS[0],)].num_values
                for c in COLUMNS:
                    dc, want = g[(c,)], patterns(ref[c].slice(off, rows), "bits")
                    if want is None:  # the flag: dictionary[indices] against the strings
                        words = np.array([bytes(w).decode() for w in dc.dictionary.to_list()])
                        same = (words[np.asarray(dc.indices)] == ref[c].slice(off, rows).to_numpy(zero_copy_only=False)).all()
                    else:
                        same = np.array_equal(np.asarray(dc.values).view(np.uint64), want)
                    if not same or (dc.double_form == "bits") != (str(ref[c].type) == "double"):
                        differ.append(c)
                off += rows
            out["month_as_bits"] = {"rows": off, "columns": len(COLUMNS), "differ": sorted(set(differ)),
                                    "equals_pyarrow": not differ and off == ref.num_rows}
    out["columns_ms_per_group"] = columns
    out["columns_sum_ms_per_group"] = sum(columns.values())

    dst = ROOT / "chiprun_out"
    dst.mkdir(exist_ok=True)
    (dst / "micro_doubles.json").write_text(json.dumps(out, indent=1) + "\n")
    print(json.dumps(out), flush=True)
    return 0 if not wrong and out["month_as_bits"]["equals_pyarrow"] else 1


if __name__ == "__main__":
    sys.exit(main())
