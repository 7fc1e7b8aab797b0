"""Span tracer tests: contextvar isolation under threads, lock-protected
merge exactness, pool attribution under the prepare pool, report() ordering,
Chrome trace-event schema, and the zero-overhead (no span allocations when
inactive) guarantee."""

import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from parquet_tpu.core.reader import FileReader
from parquet_tpu.core.writer import FileWriter
from parquet_tpu.meta.parquet_types import Type
from parquet_tpu.schema.builder import message, optional, required, string
from parquet_tpu.obs.pool import instrumented_submit
from parquet_tpu.utils import trace as trace_mod
from parquet_tpu.utils.trace import (
    add_seconds,
    add_seconds_batch,
    bump,
    decode_trace,
    span,
    stage,
)


def _write_sample(path: str, rows: int = 4000, groups: int = 2) -> str:
    schema = message(required("id", Type.INT64), optional("name", string()))
    with FileWriter(path, schema, codec="snappy") as w:
        for g in range(groups):
            w.write_rows(
                {
                    "id": int(g * rows + i),
                    "name": f"g{g}n{i % 53}" if i % 7 else None,
                }
                for i in range(rows)
            )
            w.flush_row_group()
    return path


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    return _write_sample(str(tmp_path_factory.mktemp("trace") / "t.parquet"))


def _traced_read_totals(path) -> dict:
    """{stage name: (bytes, calls)} of one fully traced host read."""
    with decode_trace() as t:
        with FileReader(path) as r:
            for i in range(r.num_row_groups):
                r.read_row_group(i)
    return {name: (s.bytes, s.calls) for name, s in t.stages.items()}


class TestThreadSafety:
    def test_eight_thread_hammer_exact_byte_totals(self, sample):
        """Regression for the pre-contextvar bug: nested decode_trace() from
        two threads clobbered the module-global and corrupted byte totals.
        Eight threads each trace their own read; every trace must hold the
        EXACT solo totals (bytes and call counts, which are deterministic —
        seconds are not)."""
        expected = _traced_read_totals(sample)
        assert expected, "solo traced read collected nothing"
        assert any(b for b, _ in expected.values()), "no byte totals collected"

        barrier = threading.Barrier(8)
        results: list = [None] * 8
        errors: list = []

        def worker(k):
            try:
                barrier.wait()
                results[k] = _traced_read_totals(sample)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors, errors
        for k, got in enumerate(results):
            assert got == expected, f"thread {k} totals diverged: {got}"

    def test_shared_trace_concurrent_merge_exact(self):
        """Many threads merging into ONE trace (the pool-worker shape): the
        lock-protected merge must lose nothing."""
        n_threads, n_iter = 8, 5000
        with decode_trace() as t:
            with ThreadPoolExecutor(max_workers=n_threads) as pool:

                def hammer():
                    for _ in range(n_iter):
                        bump("hammer", 3)

                futs = [
                    instrumented_submit(pool, hammer) for _ in range(n_threads)
                ]
                for f in futs:
                    f.result()
        s = t.stages["hammer"]
        assert s.calls == n_threads * n_iter
        assert s.bytes == 3 * n_threads * n_iter

    def test_concurrent_traces_do_not_cross_attribute(
        self, sample, tmp_path, monkeypatch
    ):
        """Two traced roundtrip reads sharing a 16-thread prepare pool: each
        trace must account exactly its own file's chunks (the explicit
        copy_context carry into pool workers), not a mix."""
        import parquet_tpu.core.reader as reader_mod

        # force the full-width pool regardless of host core count
        monkeypatch.setenv("PQT_HOST_THREADS", "16")
        pool = ThreadPoolExecutor(max_workers=16, thread_name_prefix="pqt-host")
        monkeypatch.setattr(reader_mod, "_pool", pool)
        small = _write_sample(str(tmp_path / "small.parquet"), rows=500, groups=1)

        def chunk_events(path):
            with decode_trace() as t:
                with FileReader(path, backend="tpu_roundtrip") as r:
                    for i in range(r.num_row_groups):
                        r.read_row_group(i)
            c = t.counters()
            # every chunk prepared lands on exactly one ladder rung
            return (
                c.get("prepare_fused_engaged", 0)
                + c.get("prepare_fused_declined", 0)
                + c.get("prepare_staged_chunk", 0)
            )

        expected_big = chunk_events(sample)  # 2 groups x 2 cols = 4 chunks
        expected_small = chunk_events(small)  # 1 group x 2 cols = 2 chunks
        assert expected_big == 4 and expected_small == 2

        barrier = threading.Barrier(2)
        out: dict = {}

        def run(name, path):
            barrier.wait()
            out[name] = chunk_events(path)

        a = threading.Thread(target=run, args=("big", sample))
        b = threading.Thread(target=run, args=("small", small))
        a.start(); b.start(); a.join(); b.join()
        pool.shutdown(wait=True)
        assert out == {"big": expected_big, "small": expected_small}


class TestReport:
    def test_sort_time_default_and_total_footer(self):
        with decode_trace() as t:
            add_seconds("zz_slow", 0.2, 1000)
            add_seconds("aa_fast", 0.01, 50)
        rep = t.report()
        lines = rep.splitlines()
        assert lines[-1].startswith("TOTAL")
        assert lines.index([x for x in lines if x.startswith("zz_slow")][0]) < \
            lines.index([x for x in lines if x.startswith("aa_fast")][0])
        # TOTAL sums seconds/bytes/calls
        assert "1,050 B" in lines[-1]

    def test_sort_name(self):
        with decode_trace() as t:
            add_seconds("zz_slow", 0.2)
            add_seconds("aa_fast", 0.01)
        lines = t.report(sort="name").splitlines()
        assert lines[0].startswith("aa_fast")
        assert lines[1].startswith("zz_slow")

    def test_bad_sort_raises(self):
        with decode_trace() as t:
            pass
        with pytest.raises(ValueError):
            t.report(sort="bytes")


class TestExclusiveRollup:
    """Sub-clock seconds count ONCE in rollups: a stage (or an
    add_seconds/add_seconds_batch credit) committed inside another open
    stage aggregate is part of that parent's wall time — before this fix
    the report TOTAL and the flight-recorder rollup double-counted the
    native prepare.* split against its measured parent, and every inner
    decode stage against serve.execute."""

    def test_golden_subclock_total(self):
        """The golden pin: deterministic sub-clock credits inside a
        measured parent leave TOTAL == exclusive wall, exactly."""
        with decode_trace() as t:
            add_seconds("standalone", 0.1)  # no parent open: exclusive
            with stage("parent"):
                add_seconds_batch(
                    [("prepare.decompress", 0.04), ("prepare.levels", 0.01)]
                )
                add_seconds("prepare.crc", 0.02)
        rollup = t.stage_rollup()
        # the sub-clocks carry their nested share; the exclusive stages
        # carry none
        assert rollup["prepare.decompress"]["nested_seconds"] == 0.04
        assert rollup["prepare.levels"]["nested_seconds"] == 0.01
        assert rollup["prepare.crc"]["nested_seconds"] == 0.02
        assert "nested_seconds" not in rollup["standalone"]
        assert "nested_seconds" not in rollup["parent"]
        expect = 0.1 + rollup["parent"]["seconds"]
        assert abs(t.exclusive_seconds() - expect) < 1e-9
        # the report TOTAL footer is the exclusive sum, not the inflated
        # inclusive one (which would be expect + 0.07)
        total_line = [
            ln for ln in t.report().splitlines() if ln.startswith("TOTAL")
        ][0]
        total_ms = float(total_line.split()[1])
        assert total_ms == pytest.approx(expect * 1e3, abs=0.05)
        # sub-clocked stages are marked; the parent is not
        rep = t.report()
        assert any(
            ln.startswith("prepare.decompress") and ln.endswith("*")
            for ln in rep.splitlines()
        )
        assert "(* partly sub-clocked" in rep

    def test_nested_stage_counts_once(self):
        """The serve shape: inner decode stages under serve.execute."""
        with decode_trace() as t:
            with stage("serve.execute"):
                with stage("decompress"):
                    pass
                with stage("decode"):
                    pass
        r = t.stage_rollup()
        assert r["decompress"]["nested_seconds"] == r["decompress"]["seconds"]
        assert r["decode"]["nested_seconds"] == r["decode"]["seconds"]
        assert "nested_seconds" not in r["serve.execute"]
        assert t.exclusive_seconds() == pytest.approx(
            r["serve.execute"]["seconds"], abs=1e-9
        )

    def test_same_stage_nested_and_free_splits(self):
        """One name used both inside and outside a parent: only the
        nested share is excluded from the exclusive total."""
        with decode_trace() as t:
            add_seconds("io", 0.05)  # free-standing
            with stage("serve.execute"):
                add_seconds("io", 0.03)  # nested
        r = t.stage_rollup()
        assert r["io"]["seconds"] == pytest.approx(0.08)
        assert r["io"]["nested_seconds"] == pytest.approx(0.03)
        assert t.exclusive_seconds() == pytest.approx(
            0.05 + r["serve.execute"]["seconds"], abs=1e-9
        )

    def test_span_is_not_a_parent(self):
        """A pure hierarchy span bills no seconds, so sub-clocks inside
        it (the fused native walk under the chunk.prepare span) must stay
        EXCLUSIVE — excluding them would undercount the total."""
        with decode_trace() as t:
            with span("chunk.prepare"):
                add_seconds_batch([("prepare.decompress", 0.04)])
        r = t.stage_rollup()
        assert "nested_seconds" not in r["prepare.decompress"]
        assert t.exclusive_seconds() == pytest.approx(0.04)

    def test_nesting_carries_into_pool_workers(self):
        """instrumented_submit carries the open-stage depth with the
        context: work a stage submits bills as nested on the worker."""
        pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="pqt-test")
        try:
            with decode_trace() as t:
                with stage("serve.execute"):
                    instrumented_submit(
                        pool, lambda: add_seconds("io", 0.02)
                    ).result(timeout=10)
        finally:
            pool.shutdown(wait=True)
        r = t.stage_rollup()
        assert r["io"]["nested_seconds"] == pytest.approx(0.02)


def _check_event_schema(events):
    assert events, "no trace events"
    for ev in events:
        for key in ("ph", "ts", "dur", "pid", "tid", "name"):
            assert key in ev, (key, ev)
        assert ev["ph"] in ("X", "M")
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert ev["pid"] == os.getpid()


def _check_nesting(events):
    """Complete events on one thread lane must nest or be disjoint."""
    xs = [e for e in events if e["ph"] == "X"]
    for tid in {e["tid"] for e in xs}:
        lane = sorted(
            (e for e in xs if e["tid"] == tid), key=lambda e: (e["ts"], -e["dur"])
        )
        stack = []  # open interval end times
        for e in lane:
            start, end = e["ts"], e["ts"] + e["dur"]
            while stack and start >= stack[-1] - 1e-6:
                stack.pop()
            if stack:
                assert end <= stack[-1] + 1e-3, (e, stack[-1])
            stack.append(end)


class TestChromeTrace:
    def test_schema_host_path(self, sample):
        with decode_trace() as t, span("file", {"path": sample}):
            with FileReader(sample) as r:
                for i in range(r.num_row_groups):
                    r.read_row_group(i)
        doc = t.to_chrome_trace()
        # valid JSON end to end
        doc = json.loads(json.dumps(doc))
        events = doc["traceEvents"]
        _check_event_schema(events)
        _check_nesting(events)
        names = {e["name"] for e in events}
        # the hierarchy levels all present
        for expected in ("file", "row_group", "chunk", "page", "decode_trace"):
            assert expected in names, names
        # stage leaves under them
        assert names & {"io", "decompress", "decode"}
        # thread lanes are named
        assert any(
            e["ph"] == "M" and e["name"] == "thread_name" for e in events
        )
        assert doc["otherData"]["stages"]

    def test_schema_device_pipeline_lanes_and_native_substages(self, sample):
        """The device-plan path: spans must land on the REAL worker threads
        (pqt-host/pqt-dispatch lanes) and, when the fused native walk ran,
        its internal sub-stage clocks must appear as nested spans."""
        with decode_trace() as t, span("file", {"path": sample}):
            with FileReader(sample, backend="tpu_roundtrip") as r:
                for i in range(r.num_row_groups):
                    r.read_row_group(i)
        doc = t.to_chrome_trace()
        events = doc["traceEvents"]
        _check_event_schema(events)
        _check_nesting(events)
        names = {e["name"] for e in events}
        assert "chunk.prepare" in names
        assert "dispatch" in names
        lanes = {
            e["args"]["name"] for e in events if e["ph"] == "M"
        }
        assert any(name.startswith("pqt-dispatch") for name in lanes), lanes
        if t.counters().get("prepare_fused_engaged"):
            assert any(n.startswith("prepare.") for n in names), names
            # the sub-stage spans nest inside their chunk.prepare span
            preps = [e for e in events if e["name"] == "chunk.prepare"]
            subs = [e for e in events if e["name"].startswith("prepare.")]
            for s in subs:
                assert any(
                    p["tid"] == s["tid"]
                    and p["ts"] <= s["ts"] + 1e-3
                    and s["ts"] + s["dur"] <= p["ts"] + p["dur"] + 1e-3
                    for p in preps
                ), s

    def test_add_seconds_batch_lays_spans_back_to_back(self):
        import time

        with decode_trace() as t:
            with span("outer"):
                # the batch's seconds must fit inside the enclosing span's
                # real elapsed time (as the native walk's sub-clocks do)
                time.sleep(0.006)
                add_seconds_batch([("a", 0.001), ("b", 0.002)])
        evs = [e for e in t.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
        by = {e["name"]: e for e in evs}
        a, b, outer = by["a"], by["b"], by["outer"]
        assert abs((a["ts"] + a["dur"]) - b["ts"]) < 1e-3  # contiguous
        assert outer["ts"] <= a["ts"] and b["ts"] + b["dur"] <= outer["ts"] + outer["dur"]
        assert t.stages["a"].calls == 1 and t.stages["b"].calls == 1

    def test_write_chrome_trace_file(self, sample, tmp_path):
        out = tmp_path / "trace.json"
        with decode_trace() as t:
            with FileReader(sample) as r:
                r.read_row_group(0)
        t.write_chrome_trace(str(out))
        doc = json.loads(out.read_text())
        assert doc["traceEvents"]


class TestZeroOverhead:
    def test_untraced_read_allocates_no_spans(self, sample):
        """The inactive-trace guarantee, asserted via counter (not timing):
        a read with no decode_trace active must not allocate span events."""
        # warm every lazy path first (imports, native load)
        with FileReader(sample) as r:
            r.read_row_group(0)
        before = trace_mod.span_allocations()
        with FileReader(sample) as r:
            for i in range(r.num_row_groups):
                r.read_row_group(i)
            list(r.iter_rows(row_groups=[0]))
        assert trace_mod.span_allocations() == before

    def test_stage_and_span_noop_without_trace(self):
        before = trace_mod.span_allocations()
        with stage("nothing", 10):
            pass
        with span("nothing"):
            pass
        assert trace_mod.span_allocations() == before
        assert not trace_mod.active()


class TestEventCap:
    def test_span_cap_drops_events_but_keeps_aggregates(self, monkeypatch):
        monkeypatch.setattr(trace_mod, "_MAX_EVENTS", 16)
        with decode_trace() as t:
            for _ in range(50):
                with stage("tick"):
                    pass
        assert t.stages["tick"].calls == 50  # aggregates exact past the cap
        assert t.events_dropped > 0
        assert len(t.to_chrome_trace()["traceEvents"]) <= 16 + 1  # + thread M


# -- one trace plane: the program's spans in jax.profiler's trace ---------------


def _write_device_sample(path: str, rows: int = 6000, groups: int = 2) -> str:
    """A file whose chunks take the device reader's three upload shapes: a
    dictionary-encoded int64 (hybrid expansion + gather), a
    DELTA_BINARY_PACKED int64 and a PLAIN int64."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = rows * groups
    rng = np.random.default_rng(26)
    table = pa.table(
        {
            "code": pa.array(rng.integers(0, 7, n), pa.int64()),
            "ts": pa.array(np.cumsum(rng.integers(0, 90, n)), pa.int64()),
            "plain": pa.array(rng.integers(0, 1 << 40, n), pa.int64()),
        }
    )
    pq.write_table(
        table,
        path,
        row_group_size=rows,
        compression="snappy",
        use_dictionary=["code"],
        column_encoding={"ts": "DELTA_BINARY_PACKED", "plain": "PLAIN"},
    )
    return path


@pytest.fixture(scope="module")
def device_sample(tmp_path_factory):
    return _write_device_sample(str(tmp_path_factory.mktemp("trace_dev") / "d.parquet"))


@pytest.fixture
def host_pool(monkeypatch):
    """The device reader's prepare pool at a fixed width, whatever the host's
    core count (a one-core host prepares on the calling thread)."""
    import parquet_tpu.core.reader as reader_mod

    monkeypatch.setenv("PQT_HOST_THREADS", "4")
    pool = ThreadPoolExecutor(
        max_workers=4,
        thread_name_prefix="pqt-host",
        initializer=trace_mod.name_os_thread,
    )
    monkeypatch.setattr(reader_mod, "_pool", pool)
    yield pool
    pool.shutdown(wait=True)


def _device_read(path):
    import jax

    with FileReader(path) as r:
        out = r.read_row_groups_device()
    jax.block_until_ready(
        [dc.values for group in out for dc in group.values()]
    )
    return out


class TestProfilerPlane:
    def test_device_read_leaves_pqt_events_in_the_xplane(
        self, device_sample, host_pool, tmp_path
    ):
        """Under a jax profiler session + decode_trace, the device reader's
        spans land in the written .xplane.pb as pqt:* events, on the thread
        that ran them: prepare and io on pqt-host lines, upload and launch
        inside their dispatch on the pqt-dispatch line, deliver on the
        caller's — each chunk's three carrying the same group/column."""
        import jax
        from jax.profiler import ProfileData

        _device_read(device_sample)  # compile outside the session
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with decode_trace():
                with jax.profiler.TraceAnnotation("test:window"):
                    _device_read(device_sample)
        finally:
            jax.profiler.stop_trace()
        (pb,) = tmp_path.rglob("*.xplane.pb")
        lines: dict = {}  # line name -> [(name, start, end, stats)]
        for plane in ProfileData.from_file(str(pb)).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("pqt:", "test:")):
                        # arguments ride after '#' where the runtime did not
                        # lift them into stats
                        name, _, tail = ev.name.partition("#")
                        stats = dict(ev.stats)
                        for kv in tail.rstrip("#").split(","):
                            if "=" in kv:
                                k, v = kv.split("=", 1)
                                stats.setdefault(k, v)
                        lines.setdefault(line.name, []).append(
                            (name, ev.start_ns, ev.start_ns + ev.duration_ns, stats)
                        )
        by_name: dict = {}
        for lname, evs in lines.items():
            for ev in evs:
                by_name.setdefault(ev[0], []).append((lname, ev))
        for want in (
            "pqt:chunk.prepare",
            "pqt:io.read",
            "pqt:dispatch",
            "pqt:dispatch.upload",
            "pqt:dispatch.launch",
            "pqt:deliver",
        ):
            assert want in by_name, (want, sorted(by_name))
        chunks = 2 * 3  # groups x columns
        assert len(by_name["pqt:chunk.prepare"]) == chunks
        assert len(by_name["pqt:dispatch"]) == chunks
        assert len(by_name["pqt:deliver"]) == chunks
        assert {ln for ln, _ in by_name["pqt:chunk.prepare"]} <= {
            ln for ln in lines if ln.startswith("pqt-host")
        }
        (dispatch_line,) = {ln for ln, _ in by_name["pqt:dispatch"]}
        assert dispatch_line.startswith("pqt-dispatch"), dispatch_line
        (main_line,) = {ln for ln, _ in by_name["test:window"]}
        assert {ln for ln, _ in by_name["pqt:deliver"]} == {main_line}
        dispatches = [ev for _, ev in by_name["pqt:dispatch"]]
        for inner in ("pqt:dispatch.upload", "pqt:dispatch.launch"):
            for ln, (_, s, e, _st) in by_name[inner]:
                assert ln == dispatch_line
                assert any(ds <= s and e <= de for _, ds, de, _ in dispatches), inner
        ident = lambda ev: (str(ev[3]["group"]), str(ev[3]["column"]))  # noqa: E731
        want_ids = {(str(g), c) for g in range(2) for c in ("code", "ts", "plain")}
        for name in ("pqt:chunk.prepare", "pqt:dispatch", "pqt:deliver"):
            assert {ident(ev) for _, ev in by_name[name]} == want_ids, name

    def test_jax_profile_yields_the_trace_and_writes_one_file(
        self, device_sample, tmp_path
    ):
        """The operator's entry: one `with` gives the stage table and a
        .xplane.pb that holds the pqt:* spans."""
        _device_read(device_sample)
        with trace_mod.jax_profile(str(tmp_path)) as t:
            _device_read(device_sample)
        assert t.stages["dispatch"].calls == 6 and t.stages["deliver"].calls == 6
        (pb,) = tmp_path.rglob("*.xplane.pb")
        assert b"pqt:dispatch.launch" in pb.read_bytes()

    def test_importing_the_tracer_leaves_jax_out(self):
        """The benchmark's and bench.py's jax-free processes import the
        tracer: it must never pull jax in."""
        import subprocess
        import sys

        code = (
            "import sys; import parquet_tpu.utils.trace as t\n"
            "with t.decode_trace() as tr:\n"
            "    with t.stage('s', args={'group': 0}): pass\n"
            "    with t.span('p'): pass\n"
            "assert 'jax' not in sys.modules, 'the tracer imported jax'\n"
            "assert t.annotation_allocations() == 0 and tr.stages['s'].calls == 1\n"
        )
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        subprocess.run([sys.executable, "-c", code], check=True, cwd=root, timeout=120)


class TestAnnotationOverhead:
    def test_untraced_device_read_builds_no_annotation(self, device_sample):
        """No decode_trace, no annotation object — pinned by counter, beside
        span_allocations(), not by timing."""
        _device_read(device_sample)  # warm lazy paths
        spans, anns = trace_mod.span_allocations(), trace_mod.annotation_allocations()
        _device_read(device_sample)
        with stage("nothing", 10, args={"group": 0}):
            pass
        with span("nothing"):
            pass
        assert trace_mod.span_allocations() == spans
        assert trace_mod.annotation_allocations() == anns

    def test_only_recorded_spans_build_annotations(self):
        """Under a trace: one annotation per recorded stage/span; none for
        record_span=False micro-stages nor the back-dated sub-clocks."""
        import jax  # noqa: F401 - an annotation needs jax in sys.modules

        with decode_trace() as t:
            before = trace_mod.annotation_allocations()
            for _ in range(100):
                with stage("assemble", record_span=False):
                    pass
                with trace_mod.timed_stage("assembly.rows", record_span=False):
                    pass
            add_seconds("prepare.copy", 0.001)
            add_seconds_batch([("prepare.decompress", 0.001), ("prepare.crc", 0.001)])
            bump("event")
            assert trace_mod.annotation_allocations() == before
            with stage("dispatch", args={"group": 1, "column": "a"}):
                with trace_mod.timed_stage("dataset.wait"):
                    pass
            with span("chunk.prepare", {"column": "a"}):
                pass
            assert trace_mod.annotation_allocations() == before + 3
        assert t.stages["assemble"].calls == 100
        args = [e for e in t.to_chrome_trace()["traceEvents"] if e["name"] == "dispatch"]
        assert args[0]["args"] == {  # its own args, then id / parent (the root is 0)
            "group": 1, "column": "a", "id": args[0]["args"]["id"], "parent": 0,
        }


def _hlo(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


_LOOKUP_N = 1 << 17  # indices of the three dict_gather cases below: four blocks of the dense tier's loop
_DENSE_SCOPES = ("planes", "onehot", "contract", "select", "assemble")


def _kernel_cases(pad=64):
    """(kernel, scopes its compiled HLO must carry, lowering thunk): every
    jitted kernel of device_ops at a small shape; `pad` is the length of the
    decode kernels' run / page tables."""
    import jax.numpy as jnp
    import numpy as np

    import parquet_tpu.kernels.device_ops as d

    i32 = lambda n: jnp.arange(n, dtype=jnp.int32)  # noqa: E731
    u32 = lambda n: jnp.arange(n, dtype=jnp.uint32)  # noqa: E731
    mask = jnp.asarray(np.arange(4096) % 3 == 0)
    inner_hybrid = ("unpack",)
    inner_delta = ("unpack", "prefix_sum", "rebase")
    return [
        ("hybrid_expand", inner_hybrid, lambda: _hlo(
            d.expand_hybrid_device, u32(4096 * 3 // 32), width=3, num_values=4096)),
        ("delta_decode", inner_delta, lambda: _hlo(
            d.delta_packed_decode_device, u32(4 * pad + 4096 * 40 // 32),
            nbits=64, width=40, num_values=4096, p_pad=pad)),
        ("delta_decode", inner_delta, lambda: _hlo(
            d.delta_packed_decode_device, u32(3 * pad + 4096 * 16 // 32),
            nbits=32, width=16, num_values=4096, p_pad=pad)),
        # one case a tier of the lookup (device_ops.dict_lookup_tier): XLA's
        # gather, and the dense formulation at a table of one-level size (265
        # int64 entries: 3 groups of 128) and of two-level size (4,096 int32)
        ("dict_gather", ("gather",), lambda: _hlo(
            d.dict_gather_device, i32(16).astype(jnp.int64), i32(_LOOKUP_N) % 16)),
        ("dict_gather", _DENSE_SCOPES, lambda: _hlo(
            d.dict_gather_device, i32(265).astype(jnp.int64), i32(_LOOKUP_N) % 265)),
        ("dict_gather", _DENSE_SCOPES, lambda: _hlo(
            d.dict_gather_device, i32(4096), i32(_LOOKUP_N) % 4096)),
        ("prefix_sum", (), lambda: _hlo(d.prefix_sum, i32(4096))),
        ("query_mask", ("predicate",), lambda: _hlo(d.predicate_mask_device, i32(4096), "<", 5, 5, True)),
        ("query_mask", ("lift",), lambda: _hlo(d.dict_verdict_device, mask[:16], i32(4096) % 16)),
        ("expr_agg", (), lambda: _hlo(
            d.expr_agg_device, (i32(4096).astype(jnp.int64), i32(4096)), mask,
            ("*", ("col", 0), ("-", ("lit", 100), ("col", 1))), "sum")),
        ("masked_agg", (), lambda: _hlo(d.masked_agg_device, i32(4096).astype(jnp.int64), mask, "sum")),
        ("group_agg", ("group_id", "reduce"), lambda: _hlo(
            d.group_agg_device, (i32(4096) % 3, i32(4096) % 2), jnp.asarray([6, 2, 1], jnp.int32),
            (i32(4096).astype(jnp.int64), i32(4096)), mask,
            ((("col", 0), ("sum", "min")), (("*", ("col", 0), ("-", ("lit", 100), ("col", 1))), ("sum",))))),
        ("mask_take", (), lambda: _hlo(d.mask_take_device, i32(4096), mask, out_pad=2048)),
        ("merge_mixed_numeric", (), lambda: _hlo(
            d.merge_mixed_numeric_device, i32(2048), i32(16).astype(jnp.int64),
            i32(2048).astype(jnp.int64), i32(8) % 2, i32(9) * 512, i32(8) * 256, rows_pad=4096)),
        ("merge_mixed_bytes", (), lambda: _hlo(
            d.merge_mixed_bytes_device, i32(2048), i32(17).astype(jnp.int64) * 4,
            jnp.zeros(8192, jnp.uint8), i32(2056), i32(8) % 2, i32(9) * 512, i32(8) * 256,
            i32(8).astype(jnp.int64) * 64, jnp.int32(4000), rows_pad=4096, total_bytes_pad=16384)),
        ("bss_transpose", (), lambda: _hlo(d._bss_transpose_padded, jnp.zeros((4, 4096), jnp.uint8))),
        ("record_starts", (), lambda: _hlo(d.record_starts_device, i32(4096) % 2)),
        ("list_layout", (), lambda: _hlo(d.list_layout_device, i32(4096) % 2, i32(4096) % 3, 0, 2)),
        ("list_contains_mask", (), lambda: _hlo(
            d.list_contains_mask_device, i32(4096) % 2, i32(4096) % 3, mask[:2048], 2)),
        ("bitpack_encode", (), lambda: _hlo(d.bitpack_encode_device, u32(4096) % 8, width=3)),
        ("rle_hybrid_encode", (), lambda: _hlo(d.rle_hybrid_encode_device, u32(4096) // 64, width=6)),
        ("dict_indices", (), lambda: _hlo(d.dict_indices_device, i32(4096).astype(jnp.int64) % 9)),
        ("delta_block_encode", (), lambda: _hlo(
            d.delta_block_encode_device, i32(4096).astype(jnp.int64), 4000, nbits=64)),
        ("plain_bytearray_encode", (), lambda: _hlo(
            d.plain_bytearray_encode_device, jnp.zeros(8192, jnp.uint8), i32(1025) * 8, 1000, out_pad=16384)),
    ]


_KERNEL_IDS = [
    "hybrid_expand", "delta_decode-64", "delta_decode-32",
    "dict_gather-gather", "dict_gather-dense1", "dict_gather-dense2", "prefix_sum",
    "query_mask-predicate", "query_mask-lift", "expr_agg", "masked_agg", "group_agg", "mask_take", "merge_mixed_numeric", "merge_mixed_bytes",
    "bss_transpose", "record_starts", "list_layout", "list_contains_mask", "bitpack_encode",
    "rle_hybrid_encode", "dict_indices", "delta_block_encode", "plain_bytearray_encode",
]


class TestKernelScopes:
    @pytest.mark.parametrize("k", range(len(_KERNEL_IDS)), ids=_KERNEL_IDS)
    def test_compiled_hlo_carries_the_kernel_scope(self, k):
        """The names the benchmark reads out of the device trace: every
        kernel's ops carry pqt.<kernel> in their op_name metadata, and the
        two kernels that hold the reader's device time their inner scopes."""
        import re

        name, inner, thunk = _kernel_cases()[k]
        assert _KERNEL_IDS[k].startswith(name)
        op_names = set(re.findall(r'op_name="([^"]*)"', thunk()))
        scoped = {n for n in op_names if f"/pqt.{name}/" in f"{n}/"}
        assert scoped, (name, sorted(op_names)[:8])
        for part in inner:  # under the kernel's scope, a loop's own path components allowed between
            assert any(re.search(rf"/pqt\.{name}/(.+/)?{part}/", f"{n}/") for n in scoped), (name, part)

    @pytest.mark.parametrize("k", range(3, 6), ids=_KERNEL_IDS[3:6])
    def test_a_dictionary_is_gathered_or_compared_with_never_both(self, k):
        """The dense tiers compare and contract: their compiled programs hold
        no gather, and nothing as large as indices x table (the one-hot spans
        128 entries, a block of rows at a time); the path that stays is
        exactly one gather. A table[idx] that comes back into a dense tier is
        a 9-17 ms pass per 2^20 indices on a v5e (PERF.md section 6, PR 40)."""
        import math
        import re

        _, inner, thunk = _kernel_cases()[k]
        hlo = thunk()
        gathers = len(re.findall(r" gather\(", hlo))
        if inner == ("gather",):
            assert gathers == 1 and not re.search(r" convolution\(| dot\(", hlo)
            return
        table = {"dict_gather-dense1": 265, "dict_gather-dense2": 4096}[_KERNEL_IDS[k]]
        assert gathers == 0, _KERNEL_IDS[k]
        largest = max(
            math.prod(int(x) for x in shape.split(","))
            for shape in re.findall(r"\b[a-z]+\d+\[([\d,]+)\]", hlo)
        )
        assert largest < _LOOKUP_N * table, (largest, _LOOKUP_N * table)
        assert len(re.findall(r"\bwhile\(", hlo)) == 1  # the blocks are a loop, not unrolled

    @pytest.mark.parametrize("k", range(3), ids=_KERNEL_IDS[:3])
    def test_the_segment_lookups_hold_no_loop(self, k):
        """The delta kernel's rebase is one scatter-add and one prefix sum:
        at a table length where a binary search would take 13 dependent
        gather passes over every value, the compiled kernels hold no while
        op. The hybrid kernel looks nothing up at all since it reads the
        hybrid frame: no scatter either."""
        import re

        hlo = _kernel_cases(pad=4096)[k][2]()
        op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
        lookups = {n for n in op_names if "/find_run/" in f"{n}/" or "/rebase/" in f"{n}/"}
        if k == 0:
            assert not lookups and not re.search(r" scatter\(", hlo), sorted(lookups)
        else:
            assert any(n.endswith("/scatter-add") for n in lookups), sorted(lookups)
        assert not re.search(r"\bwhile\(", hlo)
        assert not [n for n in op_names if "searchsorted" in n]

    @pytest.mark.parametrize("k", range(3), ids=_KERNEL_IDS[:3])
    def test_no_value_is_read_through_a_full_length_gather(self, k):
        """Both decode kernels read a position-indexed frame, so unpack is
        shifts and a concatenation: the hybrid kernel's compiled program
        holds no gather at all (through PR 37 it read the packed words one
        gather a value), and what the delta kernel gathers is one entry a
        PAGE under rebase (this case's page table is as long as its values).
        A table[r] that comes back is a 9 ms pass per 2^20 values on a v5e
        (PERF.md section 6)."""
        import math
        import re

        num_values = 4096  # _kernel_cases: also the table length here
        hlo = _kernel_cases(pad=4096)[k][2]()
        full = [
            op_name
            for shape, op_name in re.findall(
                r"= \w+\[([\d,]*)\]\S* gather\(.*op_name=\"([^\"]*)\"", hlo
            )
            if math.prod(int(x) for x in shape.split(",")) >= num_values
        ]
        if k == 0:
            assert not full and not re.search(r" gather\(", hlo), full
        else:
            assert full and all("/rebase/" in f"{n}/" for n in full), full
        assert not re.search(r" gather\(.*op_name=\"[^\"]*/unpack/", hlo)


class TestDispatchAccounting:
    def test_upload_bytes_and_nested_seconds(
        self, device_sample, host_pool, monkeypatch
    ):
        """dispatch.upload carries exactly the bytes of the numpy buffers
        that went to the device; upload + launch seconds commit as nested
        under dispatch, so TOTAL counts the dispatch thread's wall once."""
        import numpy as np

        import parquet_tpu.kernels.pipeline as pipe

        _device_read(device_sample)
        sent = []
        orig = pipe._ChunkPlan.dispatch_device

        def spy(plan):
            d = plan.dictionary
            n = sum(f.buf.nbytes for f in plan.frozen_hybrid)
            n += sum(f.frame.nbytes for f in plan.frozen_delta)
            if plan.frozen_hybrid and isinstance(d, np.ndarray) and d.ndim == 1:
                n += d.nbytes
            if plan.plain_host is not None:
                n += plan.plain_host.nbytes
            sent.append(n)
            return orig(plan)

        monkeypatch.setattr(pipe._ChunkPlan, "dispatch_device", spy)
        with decode_trace() as t:
            _device_read(device_sample)
        assert len(sent) == 6 and all(sent)
        r = t.stage_rollup()
        assert r["dispatch.upload"]["bytes"] == sum(sent)
        assert r["dispatch.launch"]["calls"] == 4  # code and ts, two groups; plain only uploads
        assert r["dispatch"]["calls"] == 6 and r["dispatch"]["bytes"] == 0
        assert "nested_seconds" not in r["dispatch"]
        for name in ("dispatch.upload", "dispatch.launch"):
            assert r[name]["nested_seconds"] == pytest.approx(r[name]["seconds"])
        inner = r["dispatch.upload"]["seconds"] + r["dispatch.launch"]["seconds"]
        assert 0 < inner <= r["dispatch"]["seconds"]
        assert r["io.read"]["bytes"] > 0 and r["io.read"]["calls"] == 6
        assert r["deliver"]["calls"] == 6
        flat = sum(s["seconds"] for s in r.values())
        assert t.exclusive_seconds() == pytest.approx(flat - inner)

    def test_traced_device_read_records_the_dispatch_queue_wait(
        self, device_sample, host_pool
    ):
        """Both pool hops of the device reader go through
        instrumented_submit: the wait of a prepared chunk for the single
        dispatch thread is in the trace (pool.wait) and in the registry."""
        from parquet_tpu.utils import metrics

        key = 'pool_queue_wait_seconds_count{pool="%s"}'
        before = metrics.snapshot()
        with decode_trace() as t:
            _device_read(device_sample)
        after = metrics.snapshot()
        assert t.stages["pool.wait"].calls == 12  # 6 prepares + 6 dispatches
        for pool in ("pqt-dispatch", "pqt-host"):
            assert after[key % pool] - before.get(key % pool, 0) == 6, pool


# -- PR 37: the waits are stages, and a span names the span that caused it -------


def _write_token_sample(path: str) -> str:
    """A LIST<int32> file of three small row groups: what lists="pack" reads."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(37)
    docs = [rng.integers(0, 500, int(n)).astype(np.int32) for n in rng.integers(3, 40, 90)]
    pq.write_table(
        pa.table({"input_ids": pa.array(docs, pa.list_(pa.int32()))}), path, row_group_size=30
    )
    return path


@pytest.fixture(scope="module")
def token_sample(tmp_path_factory):
    return _write_token_sample(str(tmp_path_factory.mktemp("trace_tok") / "tok.parquet"))


def _read_whole(path):
    with FileReader(path) as r:
        return r.read_row_groups_device()


def _read_by_group(path):
    with FileReader(path) as r:
        return [r.read_row_group_device(i) for i in range(r.num_row_groups)]


def _read_batches(path):
    with FileReader(path) as r:
        return list(r.iter_device_batches(1000, drop_remainder=False))


def _read_packed(path):
    with FileReader(path) as r:
        return list(r.iter_device_batches(
            4, columns=["input_ids"], lists="pack", seq_len=64, drop_remainder=False
        ))


# (the read, its file, row groups x columns, whether the prepares fan out:
# a one-chunk plan prepares on the planning thread and has no prepare to wait for)
_WAIT_SITES = {
    "read_row_groups_device": (_read_whole, "device", (2, ("code", "ts", "plain")), True),
    "read_row_group_device": (_read_by_group, "device", (2, ("code", "ts", "plain")), True),
    "iter_device_batches": (_read_batches, "device", (2, ("code", "ts", "plain")), True),
    "iter_device_batches-pack": (_read_packed, "token", (3, ("input_ids.list.element",)), False),
}


class TestWaitStages:
    """The five places where core/reader.py blocks on a pool future are
    stages: plan.wait_prepare on the planning thread (before it may enqueue
    the chunk's dispatch), plan.wait_dispatch on the consumer (before
    _deliver). Each carries its chunk's args and is recorded on the thread
    that waited."""

    @pytest.fixture
    def files(self, device_sample, token_sample):
        return {"device": device_sample, "token": token_sample}

    @pytest.mark.parametrize("site", list(_WAIT_SITES))
    def test_each_site_records_its_wait_with_the_chunks_args(self, site, files, host_pool):
        read, which, (groups, columns), fans_out = _WAIT_SITES[site]
        read(files[which])  # compile and warm lazy paths outside the trace
        with decode_trace() as t:
            read(files[which])
        chunks = {(g, c) for g in range(groups) for c in columns}
        events = [e for e in t.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
        waits = {n: [e for e in events if e["name"] == n]
                 for n in ("plan.wait_prepare", "plan.wait_dispatch")}
        wanted = ["plan.wait_prepare", "plan.wait_dispatch"] if fans_out else ["plan.wait_dispatch"]
        for name in wanted:
            assert t.stages[name].calls == len(chunks), (name, t.stages[name])
            assert {(e["args"]["group"], e["args"]["column"]) for e in waits[name]} == chunks, name
            assert {e["tid"] for e in waits[name]} == {threading.get_ident()}, name
        if not fans_out:
            assert "plan.wait_prepare" not in t.stages
        # every chunk's dispatch was enqueued after its prepare had been waited for,
        # and delivered after its dispatch had been: the waits sit between the stages
        deliver = {(e["args"]["group"], e["args"]["column"]): e for e in events
                   if e["name"] == "deliver" and "column" in e.get("args", {})}
        for e in waits["plan.wait_dispatch"]:
            d = deliver[(e["args"]["group"], e["args"]["column"])]
            assert e["ts"] + e["dur"] <= d["ts"] + 1e-3

    @pytest.mark.parametrize("site", list(_WAIT_SITES))
    def test_no_trace_no_span_and_no_annotation(self, site, files, host_pool):
        read, which, _, _ = _WAIT_SITES[site]
        read(files[which])
        spans, anns = trace_mod.span_allocations(), trace_mod.annotation_allocations()
        read(files[which])
        assert trace_mod.span_allocations() == spans
        assert trace_mod.annotation_allocations() == anns

    def test_a_wait_on_a_finished_future_is_one_stage_call(self):
        """The helper is the stage() call and nothing else: a done future
        comes back at once, traced or not."""
        from concurrent.futures import Future

        from parquet_tpu.core.reader import _wait

        fut = Future()
        fut.set_result("plan")
        before = trace_mod.span_allocations()
        assert _wait("plan.wait_dispatch", fut, 0, ("a", "b")) == "plan"
        assert trace_mod.span_allocations() == before
        with decode_trace() as t:
            assert _wait("plan.wait_dispatch", fut, 3, ("a", "b")) == "plan"
        assert t.stages["plan.wait_dispatch"].calls == 1
        (ev,) = [e for e in t.to_chrome_trace()["traceEvents"] if e["name"] == "plan.wait_dispatch"]
        assert ev["args"] == {"group": 3, "column": "a.b", "id": 1, "parent": 0}

    def test_under_query_decode_the_waits_are_nested_and_counted_once(
        self, device_sample, host_pool
    ):
        """Inside a stage (the daemon's query.decode) the waits are part of
        its wall: nested, so exclusive_seconds() and the report's TOTAL count
        them once. Under the row_group.device SPAN alone nothing encloses
        them, and they count as their own wall."""
        _read_by_group(device_sample)
        with decode_trace() as t:
            with FileReader(device_sample) as r:
                with stage("query.decode", args={"group": 0}):
                    r.read_row_group_device(0)
        roll = t.stage_rollup()
        for name in ("plan.wait_prepare", "plan.wait_dispatch"):
            assert roll[name]["calls"] == 3
            assert roll[name]["nested_seconds"] == pytest.approx(roll[name]["seconds"])
        waited = roll["plan.wait_prepare"]["seconds"] + roll["plan.wait_dispatch"]["seconds"]
        assert waited <= roll["query.decode"]["seconds"]
        assert t.exclusive_seconds() == pytest.approx(
            sum(s["seconds"] - s.get("nested_seconds", 0.0) for s in roll.values())
        )
        # what is left outside query.decode is the pool threads' own work, not the waits
        flat = sum(s["seconds"] for s in roll.values())
        assert t.exclusive_seconds() <= flat - waited + 1e-9

        with decode_trace() as bare:
            _read_by_group(device_sample)
        roll = bare.stage_rollup()
        assert "nested_seconds" not in roll["plan.wait_dispatch"]
        assert "nested_seconds" not in roll["plan.wait_prepare"]

    def test_the_new_names_are_read_by_their_own_metrics_only(self):
        """Every per-layer metric that sums stage seconds or bytes selects its
        stages by name or by prefix (benchmark/layer_metrics/*.json: e.g.
        `prepare.*`, `dispatch`, `io`, `io.read`). The stages this PR added are
        matched by the two metrics that were added to read them and by no
        other: every older metric reads exactly the stages it read."""
        import json
        from pathlib import Path

        new = ("plan.wait_prepare", "plan.wait_dispatch", "serve.parse", "serve.admit",
               "serve.plan", "serve.open_reader", "serve.respond")
        readers: dict = {name: set() for name in new}
        metrics_dir = Path(__file__).resolve().parents[1] / "benchmark" / "layer_metrics"
        for path in sorted(metrics_dir.glob("*.json")):
            spec = json.loads(path.read_text())
            for sel in spec.get("args", {}).get("stages", []):
                for name in new:
                    if name == sel or (sel.endswith("*") and name.startswith(sel[:-1])):
                        readers[name].add(spec["name"])
        assert readers == {
            "plan.wait_prepare": {"consumer_wait_ms_per_mrow"},
            "plan.wait_dispatch": {"consumer_wait_ms_per_mrow"},
            "serve.parse": {"request_host_ms_per_query"},
            "serve.admit": {"request_host_ms_per_query"},
            "serve.plan": {"request_host_ms_per_query"},
            "serve.open_reader": set(),
            "serve.respond": {"request_host_ms_per_query"},
        }


class TestSpanLinks:
    """A span event holds a small integer `id` and the id of the span that
    was open in its context when it began (`parent`); the context rides
    instrumented_submit, so a pool task's span names its submitter's."""

    def test_ids_are_unique_and_the_root_is_zero(self):
        with decode_trace() as t:
            with span("row_group.device", {"group": 0}):
                with stage("deliver"):
                    add_seconds("prepare.copy", 0.001)
            with stage("assemble", record_span=False):  # no span: no id, and no parent to anyone
                with stage("inner"):
                    pass
        ev = {e["name"]: e["args"] for e in t.to_chrome_trace()["traceEvents"] if e["ph"] == "X"}
        assert ev["decode_trace"] == {"id": 0}
        assert ev["row_group.device"] == {"group": 0, "id": 1, "parent": 0}
        assert ev["deliver"] == {"id": 2, "parent": 1}
        assert ev["prepare.copy"] == {"id": 3, "parent": 2}  # a back-dated sub-clock, under what was open
        assert ev["inner"] == {"id": 4, "parent": 0}

    def test_a_pool_tasks_span_names_the_span_open_where_it_was_submitted(self):
        pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="pqt-test")

        def prepare():
            with span("chunk.prepare", {"column": "a"}):
                with stage("io.read"):
                    pass
            return threading.get_ident()

        def dispatch():
            with stage("dispatch"):
                pass

        try:
            with decode_trace() as t:
                with span("row_group.device", {"group": 7}):
                    worker = instrumented_submit(pool, prepare).result(timeout=10)
                    with stage("plan.wait_prepare"):
                        pass
                    instrumented_submit(pool, dispatch).result(timeout=10)
                instrumented_submit(pool, dispatch).result(timeout=10)
        finally:
            pool.shutdown(wait=True)
        events = [e for e in t.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
        by_name: dict = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        (group,) = by_name["row_group.device"]
        (prep,) = by_name["chunk.prepare"]
        assert prep["tid"] == worker != group["tid"]
        assert prep["args"]["parent"] == group["args"]["id"]
        assert by_name["io.read"][0]["args"]["parent"] == prep["args"]["id"]
        # enqueued after the wait had closed: the dispatch names the span open then, not the wait
        inside, after = sorted(by_name["dispatch"], key=lambda e: e["ts"])
        assert inside["args"]["parent"] == group["args"]["id"]
        assert after["args"]["parent"] == 0
        assert len({e["args"]["id"] for e in events}) == len(events)

    def test_a_device_reads_chunks_name_their_row_group(self, device_sample, host_pool):
        _read_by_group(device_sample)
        with decode_trace() as t:
            with FileReader(device_sample) as r:
                r.read_row_group_device(1)
        events = [e for e in t.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
        (group,) = [e for e in events if e["name"] == "row_group.device"]
        gid = group["args"]["id"]
        by_id = {e["args"]["id"]: e for e in events}
        for name in ("chunk.prepare", "dispatch", "plan.wait_prepare", "plan.wait_dispatch", "deliver"):
            mine = [e for e in events if e["name"] == name]
            assert len(mine) == 3 and {e["args"]["parent"] for e in mine} == {gid}, name
        for e in events:
            if e["name"] in ("dispatch.upload", "dispatch.launch"):
                assert by_id[e["args"]["parent"]]["name"] == "dispatch"
                assert by_id[e["args"]["parent"]]["tid"] == e["tid"] != group["tid"]
            if e["name"] == "io.read":
                assert by_id[e["args"]["parent"]]["name"] == "chunk.prepare"

    def test_an_annotation_carries_parent_only_beside_its_own_args(self, monkeypatch):
        import jax

        built = []

        class Recorder:
            def __init__(self, name, **kw):
                built.append((name, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
        with decode_trace():
            with span("row_group.device", {"group": 2}):
                with stage("deliver", args={"group": 2, "column": "a"}):
                    pass
                with stage("deliver.pack"):
                    pass
        assert built == [
            ("pqt:row_group.device", {"group": 2, "parent": 0}),
            ("pqt:deliver", {"group": 2, "column": "a", "parent": 1}),
            ("pqt:deliver.pack", {}),
        ]
