"""chip_smoke.py — the quickest proof that parquet_tpu's main path runs on the chip.

    python chip_smoke.py                              # one TPU, full size
    python chip_smoke.py --platform cpu --rows 262144 # rehearsal, says so

Drives the path the README names, through the entry points a user calls, on a
seeded trip-record corpus (16M rows, 4 snappy files, 1M-row groups; the five
BASELINE.json column kinds plus a DOUBLE):

  set-up   (this process, host only) build the native library from native/,
           write the corpus with pyarrow; reference answers are pyarrow's.
  decode   (child) encoded pages up -> decode in HBM
           (FileReader.read_row_groups_device / iter_device_batches), the
           loader (ParquetDataset(device=)), then every device_ops kernel the
           read path does not reach (list layout, LIST contains, the encode
           lane through FileWriter.write_device_column).
  daemon   (child, by its CLI) `parquet-tool serve --device`, queried over
           HTTP; /v1/query units are counted by engine.

This process never imports jax: a chip belongs to one process at a time, so
each leg runs in a child that owns it alone, and the children share one
compile cache. Any failed check fails the run. The platform must be `tpu`
unless `--platform cpu` asks for a rehearsal by name — what jax happens to
find decides nothing. On success the full summary is written to
chiprun_out/chip_smoke.json and printed as a `smoke: summary {...}` line, and
the last stdout line is the verdict the driver reads, nothing more:
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
On failure there is neither.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
FILES = 4
FULL_ROWS = 16 << 20
GROUP_ROWS = 1 << 20
BATCH = 65536
MAX_LIST = 4
VENDORS = 200
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included
FLAT = ("trip_id", "vendor", "ts", "passenger_count")

# Every kernel device_ops exports, and the leg that runs it at real size: a
# kernel added later fails the smoke until it is given a leg.
KERNEL_LEGS = {
    "expand_hybrid_device": "decode",  # vendor / passenger_count / stops
    "delta_packed_decode_device": "decode",  # ts, and trip_id's repack
    "dict_gather_device": "decode",  # passenger_count's numeric dictionary; every tier in the dict_lookup check
    "double_narrow_device": "decode",  # doubles="float32": fare, and the mixed file
    "predicate_mask_device": "decode",  # filter_rows=True
    "dict_verdict_device": "daemon",  # /v1/query: a predicate on vendor, a byte-array dictionary
    "mask_take_device": "decode",
    "pack_append_device": "decode",  # stops packed: iter_device_batches(lists="pack")
    "pack_emit_device": "decode",
    "pack_carry_device": "decode",
    "record_starts_device": "kernels",
    "list_layout_device": "kernels",
    "list_contains_mask_device": "kernels",
    "dict_indices_device": "kernels",  # write_device_column: dictionary probe
    "rle_hybrid_encode_device": "kernels",  # ... dictionary index pages
    "bitpack_encode_device": "kernels",
    "delta_block_encode_device": "kernels",  # ... DELTA_BINARY_PACKED pages
    "plain_bytearray_encode_device": "kernels",  # ... PLAIN BYTE_ARRAY pages
    "masked_agg_device": "daemon",  # /v1/query device units
    "expr_agg_device": "daemon",  # ... TPC-H Q6: sum(l_extendedprice*l_discount) over a small lineitem
    "group_agg_device": "daemon",  # ... TPC-H Q1: grouped by two flag columns' resident dictionary indices
}


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


# -- the corpus (host only: numpy + pyarrow) -----------------------------------


def corpus_layout(rows: int):
    per_file = rows // FILES
    group = min(GROUP_ROWS, max(per_file // 2, 1))
    batch = BATCH if group >= 4 * BATCH else max(group // 2, 1)
    return per_file, group, batch


def write_corpus(directory: Path, rows: int, seed: int) -> list:
    """Seeded trip records over FILES snappy files. Idempotent per
    (rows, seed): a finished corpus is reused."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    per_file, group, _ = corpus_layout(rows)
    paths = [directory / f"trips-{i}.parquet" for i in range(FILES)]
    done = directory / "DONE"
    if done.exists():
        return paths
    directory.mkdir(parents=True, exist_ok=True)
    vendors = pa.array([f"vendor_{i:03d}" for i in range(VENDORS)])
    for i, path in enumerate(paths):
        rng = np.random.default_rng([seed, i])
        base = i * per_file
        lengths = rng.integers(0, MAX_LIST + 1, per_file)
        offsets = np.zeros(per_file + 1, dtype=np.int32)
        np.cumsum(lengths, out=offsets[1:])
        table = pa.table(
            {
                "trip_id": pa.array(np.arange(base, base + per_file, dtype=np.int64)),
                "vendor": pa.DictionaryArray.from_arrays(
                    pa.array(rng.integers(0, VENDORS, per_file).astype(np.int32)),
                    vendors,
                ),
                "ts": pa.array(
                    1_600_000_000_000_000
                    + base * 1000
                    + np.cumsum(rng.integers(0, 1000, per_file))
                ),
                "passenger_count": pa.array(
                    rng.integers(1, 7, per_file).astype(np.int32),
                    mask=rng.random(per_file) < 0.05,
                ),
                "fare": pa.array(np.round(rng.gamma(2.0, 9.0, per_file), 2)),
                "stops": pa.ListArray.from_arrays(
                    pa.array(offsets),
                    pa.array(rng.integers(1, 266, int(offsets[-1])).astype(np.int32)),
                ),
            }
        )
        pq.write_table(
            table,
            path,
            compression="snappy",
            row_group_size=group,
            use_dictionary=["vendor", "passenger_count", "stops.list.element"],
            column_encoding={
                "trip_id": "PLAIN",
                "ts": "DELTA_BINARY_PACKED",
                "fare": "PLAIN",
            },
        )
    done.write_text("ok\n")
    return paths


# -- the decode child: legs 1 and 2 --------------------------------------------


class CompileCounter:
    """Compile requests jax makes (one event per program, persistent-cache
    hits included) and how many of them the persistent cache answered."""

    def __init__(self):
        from jax import monitoring

        self.requests = []  # (fun_name, seconds)
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, seconds, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests.append((kw.get("fun_name", "?"), seconds))

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return len(self.requests), self.hits

    def since(self, mark) -> dict:
        new = self.requests[mark[0] :]
        return {
            "requests": len(new),
            "cache_hits": self.hits - mark[1],
            "seconds": round(sum(s for _, s in new), 2),
            "slowest": [
                [round(s, 2), n]
                for s, n in sorted(((s, n) for n, s in new), reverse=True)[:5]
            ],
        }


def counters_of(trace, prefix: str) -> dict:
    return {
        k: s.calls for k, s in sorted(trace.stages.items()) if k.startswith(prefix)
    }


def leg_decode(args) -> dict:
    import numpy as np

    import parquet_tpu.kernels.device_ops as dops  # x64 + compile cache first
    import jax
    import jax.numpy as jnp

    facts = dops.device_facts()
    # the handshake: say what jax found, then wait for the corpus
    print("@@" + json.dumps({"device": facts}), flush=True)
    if facts["platform"] != args.platform:
        raise SmokeFailure(
            f"jax found platform {facts['platform']!r}, this run needs "
            f"{args.platform!r}"
        )
    check(sys.stdin.readline().strip() == "go", "parent went away before set-up")

    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    from parquet_tpu import FileReader, FileWriter, ParquetDataset, parse_schema
    from parquet_tpu.kernels.pipeline import DeviceDoubleError
    from parquet_tpu.utils.native import require_native
    from parquet_tpu.utils.trace import decode_trace

    out: dict = {}
    compiles = CompileCounter()
    device = jax.devices()[0]
    paths = sorted(Path(args.corpus).glob("trips-*.parquet"))
    per_file, group_rows, batch = corpus_layout(args.rows)
    check(require_native().fused_gil_free, "GIL-free native binding not loaded")

    # -- doubles: measured here, independently of the library's own probe -----
    probe = np.round(np.random.default_rng(args.seed).gamma(2.0, 9.0, 4096), 2)
    doubles_exact = np.asarray(jax.device_put(probe, device)).tobytes() == probe.tobytes()
    out["doubles_exact_on_device"] = bool(doubles_exact)
    columns = list(FLAT) + (["fare"] if doubles_exact else []) + ["stops"]
    if not doubles_exact:
        # the device path must refuse, typed, rather than deliver an ulp off
        for what, run in (
            ("read_row_group_device", lambda: FileReader(str(paths[0])).read_row_group_device(0, ["fare"])),
            ("ParquetDataset(device=)", lambda: next(iter(ParquetDataset(
                str(paths[0]), batch_size=batch, columns=["fare"], device=device)))),
        ):
            try:
                run()
            except DeviceDoubleError:
                pass
            else:
                raise SmokeFailure(f"{what} delivered an inexact DOUBLE")
        say("decode: DOUBLE is not bit-exact on this device; `fare` is refused typed (DeviceDoubleError)")

    def on_device(a) -> bool:
        return {d.platform for d in a.devices()} == {args.platform}

    def np_col(table, name, off, n):
        return table.column(name).slice(off, n).combine_chunks()

    def list_lengths(dc, max_def):
        rep = np.asarray(dc.rep_levels)
        present = (np.asarray(dc.def_levels) == max_def).astype(np.int64)
        return np.add.reduceat(present, np.flatnonzero(rep == 0))

    vendor_dicts: dict = {}

    def verify_file(fi, path, groups) -> None:
        """Every delivered column of one file == the pyarrow read, bit for bit."""
        ref = pq.read_table(path)
        off = 0
        for gi, g in enumerate(groups):
            n = g[("trip_id",)].num_values
            for p, dc in g.items():
                for a in (dc.values, dc.indices, dc.dict_data, dc.dict_offsets):
                    check(a is None or on_device(a), f"{p} not resident on {args.platform}")
            for name in ("trip_id", "ts"):
                check(
                    np.array_equal(np.asarray(g[(name,)].values), np_col(ref, name, off, n).to_numpy()),
                    f"{path.name} group {gi}: {name} differs from pyarrow",
                )
            dc = g[("vendor",)]
            check(dc.indices is not None, "vendor not delivered as dictionary indices")
            words = pa.array([bytes(w).decode() for w in dc.dictionary.to_list()])
            vendor_dicts[fi, gi] = words
            check(
                np.array_equal(
                    np.asarray(dc.indices),
                    pc.index_in(np_col(ref, "vendor", off, n), value_set=words).to_numpy(),
                ),
                f"{path.name} group {gi}: vendor differs from pyarrow",
            )
            dc, want = g[("passenger_count",)], np_col(ref, "passenger_count", off, n)
            check(
                np.array_equal(np.asarray(dc.values), want.drop_null().to_numpy())
                and np.array_equal(np.asarray(dc.def_levels) == 1, want.is_valid().to_numpy(zero_copy_only=False)),
                f"{path.name} group {gi}: passenger_count differs from pyarrow",
            )
            if doubles_exact:
                got = np.asarray(g[("fare",)].values)
                check(
                    got.dtype == np.float64
                    and np.array_equal(got.view(np.uint64), np_col(ref, "fare", off, n).to_numpy().view(np.uint64)),
                    f"{path.name} group {gi}: fare differs from pyarrow",
                )
            dc, want = g[("stops", "list", "element")], np_col(ref, "stops", off, n)
            check(
                np.array_equal(np.asarray(dc.values), want.flatten().to_numpy())
                and np.array_equal(list_lengths(dc, 3), pc.list_value_length(want).to_numpy()),
                f"{path.name} group {gi}: stops differs from pyarrow",
            )
            off += n
        check(off == ref.num_rows, f"{path.name}: {off} rows delivered of {ref.num_rows}")

    def decode_pass(verify: bool) -> int:
        chunks = 0
        for fi, path in enumerate(paths):
            with FileReader(str(path)) as r:
                groups = r.read_row_groups_device(columns=columns)
            jax.block_until_ready(
                [a for g in groups for dc in g.values() for a in (dc.values, dc.indices) if a is not None]
            )
            chunks += sum(len(g) for g in groups)
            if verify:
                verify_file(fi, path, groups)
        return chunks

    # -- leg 1: decode to HBM, cold then warm ----------------------------------
    mark = compiles.mark()
    t0 = time.perf_counter()
    with decode_trace() as tr:
        chunks = decode_pass(verify=True)
    cold_s = time.perf_counter() - t0
    cold = compiles.since(mark)
    fused = counters_of(tr, "prepare_fused")
    host_pages = {c: 0 for c in columns}
    for k, v in counters_of(tr, "host_decoded_pages.").items():
        host_pages[k.split(".", 1)[1].split(".")[0]] = v
    check(fused == {"prepare_fused_engaged": chunks}, f"fused prepare counters {fused} for {chunks} chunks")
    check(all(host_pages[c] == 0 for c in FLAT), f"host-decoded pages on flat columns: {host_pages}")
    mark = compiles.mark()
    t0 = time.perf_counter()
    decode_pass(verify=False)
    warm_s = time.perf_counter() - t0
    warm = compiles.since(mark)
    check(warm["requests"] == 0, f"warm decode pass compiled: {warm}")
    out["decode"] = {
        "rows": args.rows, "chunks": chunks, "columns": columns,
        "prepare_fused_engaged": chunks, "prepare_fused_declined": 0,
        "host_decoded_pages": host_pages,
        "cold_setup_s": round(cold_s, 1), "warm_setup_s": round(warm_s, 1),
        "compile_cold": cold, "compile_warm": warm,
    }
    say(f"decode: {args.rows} rows x {len(columns)} columns resident and equal to pyarrow; "
        f"host-decoded pages {host_pages}; compile requests cold {cold['requests']} "
        f"({cold['seconds']} s), warm {warm['requests']}")

    # -- DOUBLE in the two forms a TPU holds exactly (doubles=) ----------------
    # the shapes the benchmark's corpus does not have: PLAIN pages (`fare`,
    # and `wild`: random bit patterns, overflow, subnormals, NaN, -0.0) and a
    # chunk whose dictionary outgrew its page and fell back to PLAIN mid-way
    # (`mixed`); every value against pyarrow + numpy, bit for bit
    rng = np.random.default_rng([args.seed, 99])
    n_d = group_rows
    wild = rng.integers(0, 2**64, n_d, dtype=np.uint64)
    wild[: n_d // 4] = (rng.standard_normal(n_d // 4) * 10.0 ** rng.uniform(-60, 60, n_d // 4)).view(np.uint64)
    wild[-8:] = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 5e-324, 3.4028235677973366e38, 2.0**-150]).view(np.uint64)
    mixed = np.round(rng.gamma(2.0, 9.0, n_d) * np.linspace(1, 400, n_d), 2)
    dpath = Path(args.corpus) / "doubles.parquet"
    pq.write_table(
        pa.table({"wild": pa.array(wild.view(np.float64)),
                  "mixed": pa.array(mixed, mask=rng.random(n_d) < 0.04)}),
        dpath, compression="snappy", row_group_size=n_d, use_dictionary=["mixed"],
        dictionary_pagesize_limit=256 << 10, column_encoding={"wild": "PLAIN"},
    )
    forms = {}
    for form in ("bits", "float32"):
        with decode_trace() as tr:
            with FileReader(str(paths[0])) as r:
                fare = r.read_row_groups_device(columns=["fare"], doubles=form)
            with FileReader(str(dpath)) as r:
                (dg,) = r.read_row_groups_device(doubles=form)
        got = {"fare": np.concatenate([np.asarray(g[("fare",)].values) for g in fare]),
               "wild": np.asarray(dg[("wild",)].values), "mixed": np.asarray(dg[("mixed",)].values)}
        want = {"fare": pq.read_table(paths[0], columns=["fare"])["fare"].to_numpy(),
                "wild": wild.view(np.float64), "mixed": mixed[np.asarray(dg[("mixed",)].def_levels) == 1]}
        for name, w in want.items():
            check(all(on_device(dc.values) and dc.double_form == form
                      for g in (*fare, dg) for dc in g.values()), f"doubles={form}: not resident in that form")
            if form == "bits":
                ok = got[name].dtype == np.uint64 and np.array_equal(got[name], w.view(np.uint64))
            else:
                with np.errstate(over="ignore", invalid="ignore"):
                    w32 = w.astype(np.float32)
                nan = np.isnan(w32)
                ok = (got[name].dtype == np.float32 and np.array_equal(np.isnan(got[name]), nan)
                      and np.array_equal(got[name].view(np.uint32)[~nan], w32.view(np.uint32)[~nan]))
            check(ok, f"doubles={form}: {name} differs from pyarrow + numpy")
        forms[form] = {**counters_of(tr, "device_double"), **counters_of(tr, "double_"),
                       **counters_of(tr, "hybrid_pages_repacked"), **counters_of(tr, "host_decoded_pages")}
        check(forms[form].get(f"device_double_chunks_{form}") == len(fare) + 2
              and "host_decoded_pages" not in forms[form], f"doubles={form}: counters {forms[form]}")
    check(forms["float32"].get("double_pages_narrowed_device", 0) > 0
          and forms["float32"].get("double_dict_narrowed_host", 0) == 1, f"doubles counters {forms}")
    out["doubles"] = {"rows": int(len(want["fare"]) + 2 * n_d), "forms": forms}
    say(f"doubles: fare (PLAIN), wild (PLAIN, adversarial bit patterns) and mixed (dictionary -> PLAIN) "
        f"equal pyarrow + numpy bit for bit as uint64 bits and as float32; counters {forms}")

    # -- the dictionary lookup, every tier at 2^20 indices ----------------------
    # dict_gather_device against numpy, bit for bit: tables below, inside and
    # above the dense band at both entry widths (indices past the table and
    # below it included), then a TLC-shaped file whose chunks say through the
    # two counters which formulation each took
    n_l = 1 << 20
    top = dops.DICT_DENSE_MAX
    cases = [(np.int32, 6), (np.int64, 64), (np.int64, 265), (np.uint64, 265), (np.int32, 2526),
             (np.uint32, 4096), (np.int64, 4096), (np.int32, 1 << 16), (np.uint32, top), (np.uint64, top),
             (np.int32, top + 1), (np.int64, top + 1)]
    tiers = {}
    for dt, size in cases:
        width = np.dtype(dt).itemsize
        table = rng.integers(0, 1 << (8 * width), size, dtype=f"u{width}").view(dt)
        idx = rng.integers(0, size, n_l).astype(np.int64)
        idx[:4] = [size, 2**31 - 1, -1, -(2**31)]
        idx = idx.astype(np.int32)
        got = np.asarray(dops.dict_gather_device(jnp.asarray(table), jnp.asarray(idx)))
        tier = dops.dict_lookup_tier(size, np.dtype(dt))
        check(got.dtype == table.dtype and np.array_equal(got, table[np.clip(idx, 0, size - 1)]),
              f"dict_gather_device ({tier}): {np.dtype(dt).name}[{size}] differs from numpy")
        tiers[f"{np.dtype(dt).name}[{size}]"] = tier
    check(set(tiers.values()) == {"dense", "gather"}, f"dictionary lookup tiers {tiers}")
    n_t = min(group_rows, 1 << 18)
    lpath = Path(args.corpus) / "lookup.parquet"
    zone = rng.integers(1, 266, n_t).astype(np.int64) * 1_000_003
    zone[:265] = np.arange(1, 266) * 1_000_003
    tip = rng.integers(0, 3000, n_t) / 100.0  # ~3,000 distinct amounts: a 12-bit index stream
    pq.write_table(
        pa.table({"zone": pa.array(zone), "rate": pa.array(rng.integers(1, 9, n_t).astype(np.int64)),
                  "tip": pa.array(tip, mask=rng.random(n_t) < 0.04)}),
        lpath, compression="snappy", row_group_size=n_t, use_dictionary=True,
    )
    with decode_trace() as tr:
        with FileReader(str(lpath)) as r:
            (lg,) = r.read_row_groups_device(doubles="float32")
    lookups = counters_of(tr, "dict_lookup_")
    with np.errstate(over="ignore"):
        tip32 = tip[np.asarray(lg[("tip",)].def_levels) == 1].astype(np.float32)
    check(np.array_equal(np.asarray(lg[("zone",)].values), zone)
          and np.array_equal(np.asarray(lg[("tip",)].values).view(np.uint32), tip32.view(np.uint32)),
          "lookup.parquet differs from pyarrow + numpy")
    check(lookups == {"dict_lookup_dense_chunks": 2, "dict_lookup_gather_chunks": 1}
          and not counters_of(tr, "host_decoded_pages"),
          f"dictionary lookup counters {lookups}")
    out["dict_lookup"] = {"indices": n_l, "tiers": tiers, **lookups}
    say(f"dict_lookup: every tier equals numpy bit for bit at {n_l} indices ({tiers}); counters {lookups}")

    # -- batches into a jitted step --------------------------------------------
    @jax.jit
    def step(b):
        return jax.tree_util.tree_map(lambda a: jnp.sum(a.astype(jnp.int64)), b)

    ref0 = pq.read_table(paths[0])
    n_batches = min(8, group_rows // batch)
    flat_cols = [c for c in columns if c != "fare"]  # sums are exact on ints
    with FileReader(str(paths[0])) as r:
        it = r.iter_device_batches(
            batch, columns=flat_cols, nullable="mask", lists="pad", max_list_len=MAX_LIST
        )
        for k in range(n_batches):
            b = next(it)
            check(all(on_device(a) for a in jax.tree_util.tree_leaves(b)), "batch not on device")
            got = jax.tree_util.tree_map(int, step(b))
            off = k * batch
            pcnt, stops = np_col(ref0, "passenger_count", off, batch), np_col(ref0, "stops", off, batch)
            # pyarrow writes every column optional, so each leaf arrives as
            # (values, mask) or (values, lengths): compare (sum, sum) pairs
            want = {
                ("trip_id",): (int(np_col(ref0, "trip_id", off, batch).to_numpy().sum()), batch),
                ("ts",): (int(np_col(ref0, "ts", off, batch).to_numpy().sum()), batch),
                ("vendor",): (pc.sum(pc.index_in(
                    np_col(ref0, "vendor", off, batch), value_set=vendor_dicts[0, 0])).as_py(), batch),
                ("passenger_count",): (pc.sum(pcnt).as_py(), pc.count(pcnt).as_py()),
                ("stops", "list", "element"): (
                    int(stops.flatten().to_numpy().sum()), pc.sum(pc.list_value_length(stops)).as_py()),
            }
            check({p: tuple(v) for p, v in got.items()} == want,
                  f"batch {k}: step sums {got} != pyarrow {want}")
        it.close()
    out["batches"] = {"batch": batch, "steps": n_batches, "policy": 'nullable="mask", lists="pad"'}

    # -- row-filtered batches (flat columns, DNF) -------------------------------
    fi = 1
    base = fi * per_file
    lo, hi, lo2 = base + group_rows // 3, base + group_rows + group_rows // 2, base + group_rows // 4
    dnf = [
        [("trip_id", ">=", lo), ("trip_id", "<", hi), ("passenger_count", ">=", 4)],
        [("vendor", "==", "vendor_007"), ("trip_id", "<", lo2)],
    ]
    ids = []
    with decode_trace() as tr:
        with FileReader(str(paths[fi])) as r:
            for b in r.iter_device_batches(
                batch, columns=["trip_id", "ts"], drop_remainder=False, filters=dnf, filter_rows=True
            ):
                step(b)
                ids.append(np.asarray(b[("trip_id",)]))
    t = pq.read_table(paths[fi], columns=["trip_id", "vendor", "passenger_count"])
    keep = pc.or_(
        pc.and_(pc.and_(pc.greater_equal(t["trip_id"], lo), pc.less(t["trip_id"], hi)),
                pc.fill_null(pc.greater_equal(t["passenger_count"], 4), False)),
        pc.and_(pc.equal(t["vendor"], "vendor_007"), pc.less(t["trip_id"], lo2)),
    )
    want_ids = t.filter(keep)["trip_id"].to_numpy()
    filt = counters_of(tr, "device_filter")
    check(np.array_equal(np.concatenate(ids), want_ids), "filtered row set differs from pyarrow's filter")
    check(filt.get("device_filter_engaged", 0) > 0 and "device_filter_declined" not in filt,
          f"device filter counters {filt}")
    out["filter"] = {"rows_kept": int(len(want_ids)), **filt, "device_filter_declined": 0}

    # -- the loader -------------------------------------------------------------
    ds_cols = ["trip_id", "ts", "passenger_count"] + (["fare"] if doubles_exact else [])
    ds = iter(ParquetDataset(
        str(Path(args.corpus) / "trips-*.parquet"), batch_size=batch, columns=ds_cols,
        nullable="zero", device=device,
    ))
    for k in range(4):
        b = next(ds)
        check(all(on_device(a) for a in b.values()), "loader batch not on device")
        check(np.array_equal(np.asarray(b[("trip_id",)]), np_col(ref0, "trip_id", k * batch, batch).to_numpy())
              and np.array_equal(np.asarray(b[("passenger_count",)]),
                                 pc.fill_null(np_col(ref0, "passenger_count", k * batch, batch), 0).to_numpy()),
              f"loader batch {k} differs from pyarrow")
    ds.close()
    out["loader"] = {"steps": 4, "columns": ds_cols}

    # -- packed sequences: the LIST column as [sequences, seq_len] batches -------
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reference_packed", ROOT / "benchmark" / "lib" / "reference_packed.py")
    reference = importlib.util.module_from_spec(spec)  # numpy + pyarrow: the benchmark's plain reference
    spec.loader.exec_module(reference)
    seq_len, sequences = 2048, 16
    want = reference.pack(pq.read_table(paths[2], columns=["stops"])["stops"], seq_len)
    got = [[], [], []]
    with FileReader(str(paths[2])) as r:
        for b in r.iter_device_batches(sequences, columns=["stops"], lists="pack", seq_len=seq_len,
                                       drop_remainder=False):
            check(all(on_device(a) for a in b), "packed batch not on device")
            for parts, a in zip(got, b):
                parts.append(np.asarray(a))
    check(all(np.array_equal(np.concatenate(parts), w) for parts, w in zip(got, want)),
          "packed sequences (tokens, segment ids, positions) differ from the reference")
    out["packed"] = {"sequences": int(want[0].shape[0]), "seq_len": seq_len, "batches": len(got[0])}
    say(f"batches, filter ({len(want_ids)} rows kept, counters {filt}), loader and packed sequences agree with pyarrow")

    # -- leg 2: kernels the read path does not reach ---------------------------
    check({n for n in dops.__all__ if n.endswith("_device")} == set(KERNEL_LEGS),
          "device_ops.__all__ and KERNEL_LEGS disagree")
    with FileReader(str(paths[0])) as r:
        g = r.read_row_group_device(0, flat_cols)
        n = g[("trip_id",)].num_values
        _, mask = r.read_row_group_device(0, ["stops"], filters=[("stops", "contains", 7)])
    stops_ref = np_col(ref0, "stops", 0, n)
    dc = g[("stops", "list", "element")]
    row_of, n_rows = dops.record_starts_device(jnp.asarray(np.asarray(dc.rep_levels), dtype=jnp.int32))
    check(int(n_rows) == n and int(row_of[-1]) == n - 1, "record_starts_device row count")
    offsets, _first_def, n_slots = dc.list_layout(0, 2)
    check(int(n_slots) == n and np.array_equal(np.asarray(offsets[: n + 1]), stops_ref.offsets.to_numpy()),
          "list_layout_device offsets differ from pyarrow")
    hits = np.zeros(n, dtype=bool)
    flat, offs = stops_ref.flatten().to_numpy() == 7, stops_ref.offsets.to_numpy()
    nz = np.diff(offs) > 0
    hits[nz] = np.add.reduceat(flat.astype(np.int64), offs[:-1][nz]) > 0
    check(np.array_equal(np.asarray(mask), hits), "LIST contains mask differs from pyarrow")

    # the encode lane, on resident decoded columns: delta, dictionary, PLAIN
    # int64 and PLAIN byte arrays, re-read with pyarrow
    vd = g[("vendor",)]
    width = len("vendor_000")
    vbytes = vd.dict_data.reshape(-1, width)[vd.indices].reshape(-1)
    voffs = jnp.arange(n + 1, dtype=jnp.int64) * width
    schema = parse_schema(
        "message w { required int64 ts; required int32 vendor_idx; "
        "required int64 trip_id; required binary vendor (UTF8); }"
    )
    dst = Path(args.corpus) / "device_write.parquet"
    mark = compiles.mark()
    with decode_trace() as tr:
        with FileWriter(str(dst), schema, codec="snappy", row_group_size=1 << 40,
                        column_encodings={"ts": "DELTA_BINARY_PACKED"},
                        use_dictionary=["vendor_idx"]) as w:
            w.write_device_column("ts", g[("ts",)].values)
            w.write_device_column("vendor_idx", vd.indices)
            w.write_device_column("trip_id", g[("trip_id",)].values)
            w.write_device_column("vendor", (vbytes, voffs))
    wrote = counters_of(tr, "device_write")
    check(wrote == {"device_write_engaged": 4}, f"device write counters {wrote}")
    back = pq.read_table(dst)
    check(back.num_rows == n
          and back["ts"].combine_chunks().equals(np_col(ref0, "ts", 0, n))
          and back["trip_id"].combine_chunks().equals(np_col(ref0, "trip_id", 0, n))
          and np.array_equal(back["vendor_idx"].to_numpy(), np.asarray(vd.indices))
          and back["vendor"].combine_chunks().equals(np_col(ref0, "vendor", 0, n).cast(pa.string())),
          "write_device_column output differs when re-read with pyarrow")
    encodings = sorted({e for c in pq.ParquetFile(dst).metadata.row_group(0).to_dict()["columns"] for e in c["encodings"]})
    out["kernels"] = {
        "rows": n, **wrote, "device_write_declined": 0, "encodings_written": encodings,
        "compile": compiles.since(mark),
    }
    say(f"kernels: list layout, LIST contains and the encode lane ({encodings}) agree with pyarrow")
    out["compile_total"] = compiles.since((0, 0))
    return out


# -- the parent -----------------------------------------------------------------


class Run:
    def __init__(self, args):
        self.args = args
        self.t0 = time.monotonic()
        self.children: list = []
        self.env = dict(os.environ)
        if args.platform == "cpu":
            self.env["JAX_PLATFORMS"] = "cpu"  # the rehearsal, asked for by name

    def remaining(self) -> float:
        left = DEADLINE_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise SmokeFailure(f"over the {DEADLINE_S} s budget")
        return left

    def spawn(self, cmd, **kw):
        p = subprocess.Popen(cmd, cwd=str(ROOT), env=self.env, text=True, **kw)
        self.children.append(p)
        return p

    def stop_all(self) -> None:
        for p in self.children:
            if p.poll() is None:
                p.kill()
            p.wait()


def run_decode(run: Run, corpus: Path) -> tuple:
    """Start the decode child, check the platform it found BEFORE any set-up
    work, write the corpus while it waits, then let it run."""
    a = run.args
    result = corpus.parent / f"leg_decode_{os.getpid()}.json"
    child = run.spawn(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--leg", "decode",
         "--platform", a.platform, "--rows", str(a.rows), "--seed", str(a.seed),
         "--corpus", str(corpus), "--result", str(result)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
    )
    device = None
    for line in child.stdout:
        if line.startswith("@@"):
            device = json.loads(line[2:])["device"]
            break
        print(line, end="", flush=True)
    check(device is not None, "the decode child died before naming its device")
    say(f"device: platform={device['platform']} kind={device['kind']!r} count={device['count']}")
    check(device["platform"] == a.platform,
          f"jax found platform {device['platform']!r}; this run needs {a.platform!r} "
          "(a CPU rehearsal is asked for by name: --platform cpu --rows N)")
    t0 = time.perf_counter()
    write_corpus(corpus, a.rows, a.seed)
    say(f"set-up: corpus of {a.rows} rows in {FILES} files at {corpus} ({time.perf_counter() - t0:.1f} s)")
    child.stdin.write("go\n")
    child.stdin.flush()
    for line in child.stdout:
        print(line, end="", flush=True)
    try:
        rc = child.wait(timeout=run.remaining())
    except subprocess.TimeoutExpired:
        raise SmokeFailure("decode child ran out of time") from None
    check(rc == 0, f"decode child exited {rc}")
    return device, json.loads(result.read_text())


def http(url: str, body: dict | None = None, timeout: float = 300.0):
    import urllib.request

    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read()


def query_q6(run: Run, url: str, corpus: Path, group_rows: int) -> dict:
    """TPC-H Q6 (validation parameters) over one small LINEITEM file — the
    benchmark's own corpus kind, three row groups of `group_rows` rows, the
    price column a mixed dictionary + PLAIN chunk — through the daemon's
    device lane: equal to the plain reference (pyarrow.compute over the
    decimal columns), and byte for byte the host lane's answer by its CLI."""
    import pyarrow.parquet as pq

    sys.path.insert(0, str(ROOT / "benchmark" / "lib"))
    from byname import load_by_name

    kind, ref = load_by_name("corpora", "tpch_lineitem"), load_by_name("lib", "reference_tpch")
    spec = json.loads((ROOT / "benchmark" / "configs" / "tpch-sf10-lineitem.json").read_text())["corpus"]
    spec, _ = kind.rehearsal(spec, group_rows)
    path = corpus / kind.file_name(0)
    if not path.exists():
        kind.write_file(spec, run.args.seed, 0, str(corpus), [])
    params = {"date": "1994-01-01", "discount": "0.06", "quantity": "24"}
    body = {"paths": path.name, "filters": ref.filters(params), "aggregates": ["count", ref.REVENUE]}
    raw = http(url + "/v1/query", body, timeout=run.remaining())
    r6 = json.loads(raw)
    want = ref.q6(pq.read_table(path, columns=list(ref.COLUMNS)), params)
    want = {"count": want["count"], ref.REVENUE: str(want["revenue"])}
    check(r6["result"] == want and r6["rows_scanned"] == spec["rows_per_file"],
          f"/v1/query Q6: {r6['result']} over {r6['rows_scanned']} rows != the reference {want}")
    host = subprocess.run(
        [sys.executable, "-m", "parquet_tpu.tools.parquet_tool", "scan", str(path), "--filters",
         json.dumps(body["filters"]), "--aggregate", json.dumps(body["aggregates"])],
        capture_output=True, timeout=run.remaining(), env=dict(os.environ, JAX_PLATFORMS="cpu"))
    check(host.returncode == 0 and host.stdout == raw,
          f"Q6: the host lane's CLI said {host.stdout[-300:]!r} {host.stderr[-300:]!r}, the daemon {raw!r}")
    return r6


def query_q1(run: Run, url: str, corpus: Path, group_rows: int) -> dict:
    """TPC-H Q1 (validation parameter) over the same small LINEITEM file,
    grouped on the daemon's device lane: every group equal to the plain
    reference (benchmark/lib/reference_tpch_q1.py) to the last digit, and the
    body byte for byte the host lane's answer by its CLI."""
    import pyarrow.parquet as pq

    from byname import load_by_name

    kind, ref = load_by_name("corpora", "tpch_lineitem_q1"), load_by_name("lib", "reference_tpch_q1")
    spec = json.loads((ROOT / "benchmark" / "configs" / "tpch-sf10-pricing-summary.json").read_text())["corpus"]
    spec, _ = kind.rehearsal(spec, group_rows)
    path = corpus / kind.file_name(0)
    if not path.exists():  # tpch_lineitem's bytes: query_q6 has written them
        kind.write_file(spec, run.args.seed, 0, str(corpus), [])
    params = {"delta": "90"}
    body = {"paths": path.name, "filters": ref.filters(params), "group_by": list(ref.GROUP_BY),
            "aggregates": list(ref.AGGREGATES)}
    raw = http(url + "/v1/query", body, timeout=run.remaining())
    r1 = json.loads(raw)
    share = ref.q1(pq.read_table(path, columns=list(ref.COLUMNS)), params)
    want = ref.merge([[[f, s, sums] for (f, s), sums in sorted(share.items())]])
    check(r1["groups"] == want and r1["group_count"] == len(want) == 4 and r1["rows_scanned"] == spec["rows_per_file"],
          f"/v1/query Q1: {r1['groups']} over {r1['rows_scanned']} rows != the reference {want}")
    host = subprocess.run(
        [sys.executable, "-m", "parquet_tpu.tools.parquet_tool", "scan", str(path), "--filters",
         json.dumps(body["filters"]), "--aggregate", json.dumps(body["aggregates"]), "--group-by",
         ",".join(body["group_by"])],
        capture_output=True, timeout=run.remaining(), env=dict(os.environ, JAX_PLATFORMS="cpu"))
    check(host.returncode == 0 and host.stdout == raw,
          f"Q1: the host lane's CLI said {host.stdout[-300:]!r} {host.stderr[-300:]!r}, the daemon {raw[-300:]!r}")
    return r1


def run_daemon(run: Run, corpus: Path) -> dict:
    """The daemon by its CLI, asked over HTTP; answers are pyarrow's."""
    import re

    import pyarrow.compute as pc
    import pyarrow.dataset as pads

    a = run.args
    per_file, group_rows, _ = corpus_layout(a.rows)
    child = run.spawn(
        [sys.executable, "-m", "parquet_tpu.tools.parquet_tool", "serve", "--device",
         "--root", str(corpus), "--port", "0", "--timeout-s", "0"],
        stdout=subprocess.PIPE,
    )
    url = said_device = None
    for line in child.stdout:
        print(line, end="", flush=True)
        if m := re.search(r"serve: listening on (http://\S+)", line):
            url = m.group(1)
        if m := re.search(r"serve: device (\S+) '([^']*)'", line):
            said_device = m.groups()
            break
    check(url and said_device, "the daemon never reported its address and device")
    check(said_device[0] == a.platform, f"the daemon holds platform {said_device[0]!r}")
    health = json.loads(http(url + "/healthz"))
    check(health["device"]["platform"] == a.platform and health["device"]["kind"] == said_device[1],
          f"/healthz names device {health.get('device')}")

    table = pads.dataset([str(p) for p in sorted(corpus.glob("trips-*.parquet"))]).to_table(
        columns=["trip_id", "vendor", "ts", "passenger_count"])
    lo, hi = per_file // 2, a.rows - per_file // 2
    q1 = {
        "paths": "trips-*.parquet",
        "filters": [[["trip_id", ">=", lo], ["trip_id", "<", hi], ["passenger_count", ">=", 3]],
                    [["trip_id", "<", group_rows // 8]]],
        "aggregates": ["count", ["sum", "trip_id"], ["min", "ts"], ["max", "ts"]],
    }
    m1 = pc.or_(
        pc.and_(pc.and_(pc.greater_equal(table["trip_id"], lo), pc.less(table["trip_id"], hi)),
                pc.fill_null(pc.greater_equal(table["passenger_count"], 3), False)),
        pc.less(table["trip_id"], group_rows // 8))
    t1 = table.filter(m1)
    want1 = {"count": t1.num_rows, "sum(trip_id)": pc.sum(t1["trip_id"]).as_py(),
             "min(ts)": pc.min(t1["ts"]).as_py(), "max(ts)": pc.max(t1["ts"]).as_py()}
    ts_cut = pc.quantile(table["ts"], 0.4)[0].as_py()
    q2 = {
        "paths": "trips-*.parquet",
        "filters": [[["ts", ">", int(ts_cut)], ["passenger_count", "<", 5]]],
        "aggregates": ["count", ["count", "passenger_count"], ["sum", "passenger_count"],
                       ["min", "trip_id"], ["max", "passenger_count"]],
    }
    t2 = table.filter(pc.and_(pc.greater(table["ts"], int(ts_cut)),
                              pc.fill_null(pc.less(table["passenger_count"], 5), False)))
    want2 = {"count": t2.num_rows, "count(passenger_count)": pc.count(t2["passenger_count"]).as_py(),
             "sum(passenger_count)": pc.sum(t2["passenger_count"]).as_py(),
             "min(trip_id)": pc.min(t2["trip_id"]).as_py(),
             "max(passenger_count)": pc.max(t2["passenger_count"]).as_py()}
    q3 = {
        "paths": "trips-*.parquet",
        "filters": [[["trip_id", "<", per_file]]],
        "group_by": ["vendor"],
        "aggregates": ["count", ["sum", "passenger_count"]],
    }
    t3 = table.filter(pc.less(table["trip_id"], per_file)).group_by("vendor").aggregate(
        [([], "count_all"), ("passenger_count", "sum")])
    want3 = {v: (c, s) for v, c, s in zip(
        t3["vendor"].to_pylist(), t3["count_all"].to_pylist(), t3["passenger_count_sum"].to_pylist())}

    q4 = {
        "paths": "trips-*.parquet",
        "filters": [["vendor", "==", "vendor_007"]],
        "aggregates": ["count", ["sum", "trip_id"]],
    }
    t4 = table.filter(pc.equal(table["vendor"].cast("string"), "vendor_007"))
    want4 = {"count": t4.num_rows, "sum(trip_id)": pc.sum(t4["trip_id"]).as_py()}

    r1 = json.loads(http(url + "/v1/query", q1, timeout=run.remaining()))
    check(r1["result"] == want1, f"/v1/query 1: {r1['result']} != pyarrow {want1}")
    r2 = json.loads(http(url + "/v1/query", q2, timeout=run.remaining()))
    check(r2["result"] == want2, f"/v1/query 2: {r2['result']} != pyarrow {want2}")
    r3 = json.loads(http(url + "/v1/query", q3, timeout=run.remaining()))
    got3 = {g["key"][0]: (g["aggregates"]["count"], g["aggregates"]["sum(passenger_count)"])
            for g in r3["groups"]}
    check(got3 == want3, "/v1/query group_by differs from pyarrow")
    r4 = json.loads(http(url + "/v1/query", q4, timeout=run.remaining()))
    check(r4["result"] == want4, f"/v1/query 4: {r4['result']} != pyarrow {want4}")
    r6 = query_q6(run, url, corpus, group_rows)
    rq1 = query_q1(run, url, corpus, group_rows)
    rows = [json.loads(x) for x in http(
        url + "/v1/scan",
        {"paths": "trips-0.parquet", "columns": ["trip_id", "vendor", "passenger_count"], "limit": 1000},
        timeout=run.remaining()).splitlines() if x.strip()]
    head = table.slice(0, 1000).select(["trip_id", "vendor", "passenger_count"]).to_pylist()
    check(rows == head, "/v1/scan rows differ from pyarrow")

    metrics = http(url + "/metrics").decode()

    def units(engine: str) -> int:
        m = re.search(r'query_device_units_total\{engine="%s"\} (\d+)' % engine, metrics)
        return int(m.group(1)) if m else 0

    got = {"device": units("device"), "host_fallback": units("host_fallback")}
    # q3 groups by vendor under sum(passenger_count), an input with nulls: the grouped kernel's typed
    # decline (input_shape), so its units stay the host's
    want = {"device": r1["units"] + r2["units"] + r4["units"] + r6["units"] + rq1["units"],
            "host_fallback": r3["units"]}
    check(got == want, f"query units by engine {got}, expected {want}")
    grouped = re.search(r"query_group_units (\d+)", metrics)
    check(grouped and int(grouped.group(1)) == rq1["units"],
          f"query_group_units {grouped and grouped.group(1)}, expected Q1's {rq1['units']}")
    child.send_signal(signal.SIGTERM)
    tail = child.stdout.read()
    print(tail, end="", flush=True)
    try:
        rc = child.wait(timeout=min(60, run.remaining()))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("the daemon did not drain on SIGTERM") from None
    check(rc == 0 and "serve: drained, bye" in tail, f"the daemon exited {rc} without draining")
    say(f"daemon: 4 queries + 1 scan equal pyarrow, Q6 and Q1 (grouped in HBM) equal their references and the host lane's bytes; "
        f"units by engine {got}; drained")
    return {
        "device": health["device"], "query_units": [r1["units"], r2["units"], r3["units"], r4["units"], r6["units"], rq1["units"]],
        "query_device_units": got, "unexpected_host_fallback_units": 0, "scan_rows": len(rows),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=FULL_ROWS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="cpu is a rehearsal and must be asked for by name")
    ap.add_argument("--workdir", default=str(ROOT / ".smoke"))
    ap.add_argument("--leg", choices=("decode",), help=argparse.SUPPRESS)
    ap.add_argument("--corpus", help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.rows < FILES * 1024 or args.rows % FILES:
        ap.error(f"--rows must be a multiple of {FILES} and at least {FILES * 1024}")

    if args.leg == "decode":
        try:
            out = leg_decode(args)
        except SmokeFailure as e:
            print(f"smoke: FAILED in the decode child: {e}", file=sys.stderr, flush=True)
            return 1
        Path(args.result).write_text(json.dumps(out))
        return 0

    rehearsal = args.platform == "cpu"
    if rehearsal:
        say("THIS IS A REHEARSAL ON THE CPU (--platform cpu): nothing here is a device fact")
    run = Run(args)
    summary: dict = {"ok": False}
    try:
        try:
            from parquet_tpu.utils.native import require_native  # no jax behind it
        except ImportError as e:
            raise SmokeFailure(f"the parquet_tpu package is not beside this script: {e}") from None
        t0 = time.perf_counter()
        try:
            require_native()
        except RuntimeError as e:
            raise SmokeFailure(str(e)) from None
        say(f"set-up: native library and _native_ext binding ready ({time.perf_counter() - t0:.1f} s)")
        corpus = Path(args.workdir) / f"corpus-{args.rows}-{args.seed}"
        device, decoded = run_decode(run, corpus)
        daemon = run_daemon(run, corpus)
        summary = {
            "ok": True,
            "device": {k: device[k] for k in ("platform", "kind", "count")},
            "mode": "rehearsal, cpu" if rehearsal else "chip",
            "rows": args.rows,
            "cut": None if args.rows == FULL_ROWS else f"{args.rows} of {FULL_ROWS} rows",
            "legs": {"decode": "passed", "kernels": "passed", "daemon": "passed"},
            **decoded,
            "daemon": daemon,
            "kernel_legs": KERNEL_LEGS,
            "wall_s": round(time.monotonic() - run.t0, 1),
            "claim": None,
        }
    except SmokeFailure as e:
        print(f"smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        run.stop_all()
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / "chip_smoke.json").write_text(json.dumps(summary, indent=1) + "\n")
    say("summary " + json.dumps(summary))
    # the last line is the driver's: exactly these keys, nothing beside them
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
