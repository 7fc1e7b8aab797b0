"""Hierarchical span tracing + per-stage decode instrumentation.

The reference has no observability at all (SURVEY §5: 'no pprof hooks, no
timing instrumentation'). This module provides the opt-in, per-read layer:
a `decode_trace()` collects BOTH flat per-stage aggregates (wall time, bytes,
calls — the report() table) and hierarchical spans (file → row-group → chunk
→ page → stage, including the native prepare sub-clocks) exportable as Chrome
trace-event JSON for Perfetto / chrome://tracing. The always-on process
counters live in utils/metrics.py; `bump()` dual-reports into them.

Zero overhead when no trace is active: one contextvar read, no span
allocations and no profiler annotation built (asserted by test via the
span_allocations() / annotation_allocations() counters).

One trace plane: while a trace is active, every stage()/timed_stage()/span()
that records a span also holds a `jax.profiler.TraceAnnotation("pqt:<name>")`
open for the same interval on the same thread, so a jax profiler session
(jax_profile(), or the benchmark's --trace 1) shows the program's own spans
on the profiler's clock beside the device's ops — idle gaps of the device
attribute to prepare / upload / launch / deliver by what was open then.
The spans of a device read and of a daemon request, by the thread that
holds them (the catalogue; PERF.md section 3 says which metric reads which):

  consumer / planning thread   row_group.device (span) > plan.wait_prepare
                               (blocked on a chunk's prepare future, before it
                               may enqueue the chunk's dispatch), plan.wait_dispatch
                               (blocked on a dispatched plan, before deliver),
                               deliver > deliver.pack; query.take
  pqt-host_*                   chunk.prepare (span) > io.read, and the
                               back-dated prepare.* sub-clocks
  pqt-dispatch_0               dispatch > dispatch.upload, dispatch.launch
  request handler              serve.parse, serve.admit (the gate; the byte
                               charge after the plan is a span of the same
                               name), serve.plan, serve.merge, serve.respond
                               (serve.stream around a scan's chunked write).
                               _finish (recorder, cost ledger) runs after the
                               request's trace has closed: unclocked
  pqt-serve_*                  serve.aggregate / serve.execute > serve.open_reader
                               (the close is a span of the same name),
                               query.decode > [the read above], query.mask,
                               query.aggregate > query.group_keys (a grouped
                               unit's dictionaries to key values and its
                               slot -> key table), query.sync

Counters of a device read that no stage carries (count() / bump(); PERF.md
section 3's audit says what reads each): dict_lookup_dense_chunks /
dict_lookup_gather_chunks — one a numeric dictionary chunk expanded in HBM,
by the formulation its table's length and dtype pick
(device_ops.dict_lookup_tier) — beside hybrid_values_framed,
delta_values_framed, mixed_chunks_by_segments, padded_delivery_exact_chunks.

The waits are time WAITED beside the producers' time busy: where an idle gap
of the device falls under a wait and under no producer on any thread, it is
the hop between two threads (benchmark/lib/xsweep.py).

The span that caused it: every span event holds a small integer `id` and
the id of the span that was open in its context when it began (`parent`;
the root span, decode_trace, is 0). The open span is a contextvar beside the
trace, so it rides instrumented_submit's copy_context(): a chunk.prepare on a
pqt-host thread names the row_group.device (or query.decode) that submitted
it, a dispatch on pqt-dispatch the span open on the planning thread that
enqueued it. to_chrome_trace() writes both under `args`; an annotation that
carries args carries `parent` too. Nothing in the program reads them.

This module never imports jax: it looks it up in sys.modules, and a process
that never imported jax has no profiler session to annotate. Not annotated:
record_span=False stages (per-row micro-stages) and the back-dated
add_seconds()/add_seconds_batch() sub-clocks (an annotation cannot be
back-dated; their enclosing span carries them).

Thread model: the active trace propagates through a `contextvars.ContextVar`,
so concurrent traces on different threads are ISOLATED (the old module-global
was racy under the 16-thread prepare pool), while pool workers doing a traced
read's prepare/dispatch work join the submitting read's trace via
`obs.pool.instrumented_submit()` (an explicit `copy_context()` carry —
ThreadPoolExecutor does not propagate context by itself — which also
records the task's queue wait as the `pool.wait` stage). All merges into a
shared trace are lock-protected.

    from parquet_tpu.utils.trace import decode_trace

    with decode_trace() as t:
        reader.read_row_group(0)
    print(t.report())                 # per-stage table, hottest first
    t.write_chrome_trace("trace.json")  # load in ui.perfetto.dev

    with jax_profile("/tmp/trace") as t:  # jax.profiler.trace + decode_trace
        reader.read_row_groups_device()   # device ops AND pqt:* spans in one
    print(t.report())                     # .xplane.pb (TensorBoard/XProf)
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass

from . import metrics as _metrics

__all__ = [
    "decode_trace",
    "stage",
    "timed_stage",
    "span",
    "add_bytes",
    "add_seconds",
    "add_seconds_batch",
    "bump",
    "count",
    "active",
    "current",
    "span_allocations",
    "annotation_allocations",
    "jax_profile",
    "name_os_thread",
    "DecodeTrace",
]

_active_var: ContextVar = ContextVar("pqt_decode_trace", default=None)

# Depth of stage() / timed_stage() aggregates currently OPEN in this
# context. Seconds committed while an enclosing stage aggregate is open
# (an inner decode stage under serve.execute, a native sub-clock inside a
# measured parent) are already part of that parent's wall time — they are
# marked "nested" on their own StageStats so rollups and the report TOTAL
# can count them EXACTLY once. The contextvar rides the same
# copy_context() carry as the trace itself, so nesting detected inside a
# pool worker attributes against the stage open on that worker.
_stage_depth_var: ContextVar = ContextVar("pqt_stage_depth", default=0)

# The id of the span OPEN in this context (None outside any trace): what a
# span records as its `parent` when it begins. It rides copy_context() like
# the two above, so a pool task's first span names the span that was open on
# the thread that submitted it — a chunk.prepare on a pqt-host thread its
# row_group.device or query.decode, a dispatch on pqt-dispatch the span open
# on the planning thread that enqueued it. Ids are small integers, per trace;
# the root span (decode_trace) is 0.
_span_var: ContextVar = ContextVar("pqt_span", default=None)

# Process-wide count of span-event allocations: the zero-overhead oracle.
# A read with no trace active must leave it untouched — tests assert that by
# counter, not timing. Mutated only while some trace's lock is held, so the
# count is exact for single-trace workloads and best-effort across
# concurrently active traces.
_span_allocs = 0

# Process-wide count of profiler annotations built: the same oracle for the
# jax.profiler.TraceAnnotation side. Only a span recorded under an active
# trace, in a process that has imported jax, moves it.
_annotation_allocs = 0

# Per-trace span cap: a traced 10M-row assembled read bills stage("assemble")
# per row; past the cap events drop (counted in events_dropped) while the
# stage AGGREGATES stay exact.
_MAX_EVENTS = 1 << 17


@dataclass
class StageStats:
    seconds: float = 0.0
    bytes: int = 0
    calls: int = 0
    # the share of `seconds` that elapsed INSIDE another open stage
    # aggregate (a sub-clock): already billed to the enclosing stage, so
    # exclusive rollups subtract it — TOTAL counts wall time once
    nested_seconds: float = 0.0


class DecodeTrace:
    """One read's collected stages + spans. Safe to mutate from many threads
    (every merge takes the trace lock); read it after the `with` block."""

    def __init__(self):
        self.stages: dict[str, StageStats] = {}
        self.events_dropped = 0
        # cross-process propagation key (obs/propagate.py): an opaque
        # 32-hex trace-id set by whoever opened the request scope, or None
        # for library reads outside any scope. Carried into the Chrome
        # export so trace-merge can stitch multi-process documents.
        self.trace_id: str | None = None
        self._lock = threading.Lock()
        self._t0 = time.perf_counter_ns()
        # finished spans: (name, tid, start_ns rel to _t0, dur_ns, args|None,
        # id, parent id|None)
        self._events: list[tuple] = []
        self._ids = itertools.count(1)  # 0 is the root span's
        self._threads: dict[int, str] = {}

    # -- collection (lock-protected merge; called from pool threads) ----------

    def _stat(self, name: str) -> StageStats:
        # caller holds self._lock
        s = self.stages.get(name)
        if s is None:
            s = self.stages[name] = StageStats()
        return s

    def _commit(
        self,
        name: str,
        seconds: float = 0.0,
        nbytes: int = 0,
        calls: int = 0,
        start_ns: int | None = None,
        dur_ns: int = 0,
        args: dict | None = None,
        nested: bool = False,
        ident: int | None = None,
        parent: int | None = None,
    ) -> None:
        global _span_allocs
        with self._lock:
            if calls or nbytes or seconds:
                s = self._stat(name)
                s.seconds += seconds
                s.bytes += nbytes
                s.calls += calls
                if nested:
                    s.nested_seconds += seconds
            if start_ns is not None:
                tid = threading.get_ident()
                if tid not in self._threads:
                    self._threads[tid] = threading.current_thread().name
                if len(self._events) >= _MAX_EVENTS:
                    self.events_dropped += 1
                else:
                    _span_allocs += 1
                    if ident is None:  # a back-dated sub-clock: never open
                        ident, parent = next(self._ids), _span_var.get()
                    self._events.append(
                        (name, tid, start_ns - self._t0, dur_ns, args, ident, parent)
                    )

    # -- reporting -------------------------------------------------------------

    def counters(self) -> dict:
        """{name: calls} for every bump()-style event collected — the
        robustness counters ride here: prepare_fused_engaged/_declined,
        prepare_fused_fault_<stage>, prepare_fallback_recovered,
        chunks_quarantined, chunks_nulled, row_groups_quarantined."""
        with self._lock:
            return {name: s.calls for name, s in self.stages.items() if s.calls}

    def stage_rollup(self) -> dict:
        """The flat per-stage aggregates as plain JSON-shaped data:
        {stage: {"seconds", "bytes", "calls"}} — what the flight recorder
        stores per request (the span TREE is sampled; this rollup is kept
        for every record, and its pool.wait entry is the record's
        queue-wait). Stages whose time elapsed inside another measured
        stage (sub-clocks: the native prepare.* split, an inner decode
        stage under serve.execute) additionally carry "nested_seconds" —
        the share already billed to their parent — so a consumer summing
        `seconds - nested_seconds` counts wall time exactly once."""
        with self._lock:
            out = {}
            for n, s in self.stages.items():
                d = {"seconds": s.seconds, "bytes": s.bytes, "calls": s.calls}
                if s.nested_seconds:
                    d["nested_seconds"] = s.nested_seconds
                out[n] = d
            return out

    def exclusive_seconds(self) -> float:
        """Wall seconds across all stages with sub-clock time counted
        ONCE: sum of per-stage (seconds - nested_seconds) — the same
        quantity the report() TOTAL footer shows (computed there inline,
        atomically with its per-stage listing); exposed as API for
        embedders and tests."""
        with self._lock:
            return sum(
                s.seconds - s.nested_seconds for s in self.stages.values()
            )

    def report(self, sort: str = "time") -> str:
        """Per-stage table. sort="time" (default) lists the hottest stages
        first (wall seconds, descending); sort="name" is alphabetical.
        A TOTAL footer sums seconds/bytes/calls across stages; sub-clock
        seconds (time a stage spent inside another measured stage — the
        native prepare.* split under its parent, inner decode stages under
        serve.execute) count toward the TOTAL exactly once, and stages
        that are partly or wholly sub-clocks are marked with a trailing
        `*` (their own line still shows inclusive seconds)."""
        if sort not in ("time", "name"):
            raise ValueError(f'report sort must be "time" or "name", got {sort!r}')
        with self._lock:
            items = [
                (n, s.seconds, s.bytes, s.calls, s.nested_seconds)
                for n, s in self.stages.items()
            ]
        if sort == "name":
            items.sort(key=lambda kv: kv[0])
        else:
            items.sort(key=lambda kv: (-kv[1], kv[0]))

        def line(name, seconds, nbytes, calls, mark=""):
            rate = f" ({nbytes / seconds / 1e6:.0f} MB/s)" if seconds > 0 and nbytes else ""
            return (
                f"{name:12s} {seconds * 1000:8.1f} ms  {nbytes:>12,} B  "
                f"{calls:>6} calls{rate}{mark}"
            )

        lines = [
            line(n, sec, b, c, "  *" if nested else "")
            for n, sec, b, c, nested in items
        ]
        lines.append(
            line(
                "TOTAL",
                sum(sec - nested for _, sec, _b, _c, nested in items),
                sum(b for _, _s, b, _c, _n in items),
                sum(c for _, _s, _b, c, _n in items),
            )
        )
        if any(nested for *_rest, nested in items):
            lines.append(
                "(* partly sub-clocked: time also inside an enclosing "
                "stage; TOTAL counts it once)"
            )
        return "\n".join(lines)

    # -- Chrome trace-event export ---------------------------------------------

    def to_chrome_trace(self) -> dict:
        """The collected spans as a Chrome trace-event JSON object (the
        format Perfetto and chrome://tracing load). Every span is a complete
        ("X") event with microsecond ts/dur relative to trace start, on its
        real thread lane; one thread_name metadata ("M") event names each
        lane (MainThread / pqt-host_* / pqt-dispatch_*). Every span's args
        hold its `id` and, but for the root, `parent`: the id of the span
        that was open in its context when it began — across a pool hop, the
        submitter's. Aggregates and bump() counters ride in otherData."""
        pid = os.getpid()
        with self._lock:
            events = list(self._events)
            threads = dict(self._threads)
            stages = {
                n: {
                    "seconds": s.seconds,
                    "bytes": s.bytes,
                    "calls": s.calls,
                    **(
                        {"nested_seconds": s.nested_seconds}
                        if s.nested_seconds
                        else {}
                    ),
                }
                for n, s in self.stages.items()
            }
            dropped = self.events_dropped
        out = [
            {
                "ph": "M",
                "name": "thread_name",
                "pid": pid,
                "tid": tid,
                "ts": 0,
                "dur": 0,
                "args": {"name": tname},
            }
            for tid, tname in sorted(threads.items())
        ]
        events.sort(key=lambda e: (e[1], e[2], -e[3]))  # (tid, start, -dur)
        for name, tid, rel_ns, dur_ns, args, ident, parent in events:
            links = {"id": ident} if parent is None else {"id": ident, "parent": parent}
            out.append({
                "ph": "X",
                "name": name,
                "cat": name.split(".", 1)[0],
                "pid": pid,
                "tid": tid,
                "ts": rel_ns / 1e3,
                "dur": dur_ns / 1e3,
                "args": {**(args or {}), **links},
            })
        doc = {
            "traceEvents": out,
            "displayTimeUnit": "ms",
            "otherData": {
                "stages": stages,
                "counters": {n: v["calls"] for n, v in stages.items() if v["calls"]},
                "events_dropped": dropped,
            },
        }
        if self.trace_id is not None:
            doc["otherData"]["propagation"] = {"trace_id": self.trace_id}
        return doc

    def write_chrome_trace(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


def _open_annotation(name: str, args: dict | None, parent: int | None = None):
    """Enter a jax.profiler.TraceAnnotation("pqt:<name>", **args) on this
    thread and return it (None where jax was never imported: no profiler
    session can exist). An annotation that carries args also carries its
    span's `parent`; one without stays a bare name. Outside a profiler
    session the annotation is jax's own no-op. Called only under an active
    trace."""
    global _annotation_allocs
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)  # None too while jax is mid-import
    if profiler is None:
        return None
    _annotation_allocs += 1
    if args and parent is not None:
        args = {**args, "parent": parent}
    ann = profiler.TraceAnnotation("pqt:" + name, **(args or {}))
    ann.__enter__()
    return ann


def _close_annotation(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


@contextmanager
def decode_trace():
    """Activate stage + span collection for the enclosed reads (this thread,
    plus any pool work submitted from it via obs.pool.instrumented_submit).
    Nested traces
    shadow; traces on OTHER threads are unaffected (contextvar isolation)."""
    t = DecodeTrace()
    token = _active_var.set(t)
    span_token = _span_var.set(0)
    try:
        yield t
    finally:
        _span_var.reset(span_token)
        _active_var.reset(token)
        # root span: the whole traced region, on the activating thread
        t._commit(
            "decode_trace",
            start_ns=t._t0,
            dur_ns=time.perf_counter_ns() - t._t0,
            ident=0,
        )


def _enter_stage() -> tuple:
    """Open a stage aggregate in this context: returns (reset token,
    was-nested). The depth rides the same contextvar carry as the trace,
    so sub-clocks committed inside a pool task see the stage their
    submitter (or the task itself) holds open."""
    depth = _stage_depth_var.get()
    return _stage_depth_var.set(depth + 1), depth > 0


def _reset(var: ContextVar, token) -> None:
    try:
        var.reset(token)
    except ValueError:  # pragma: no cover - exotic cross-context consumer
        # a generator suspended inside the stage was resumed from another
        # context: losing the reset mis-tags later commits there as
        # nested (or under a closed parent) at worst — never break the
        # decode over bookkeeping
        pass


_NO_SPAN = (None, None, None, None)  # what a record_span=False stage opens


def _open_span(t: DecodeTrace, name: str, args: dict | None) -> tuple:
    """Begin a recorded span in this context: (its id, the id of the span
    that was open here, the reset token, its profiler annotation)."""
    ident = next(t._ids)
    parent = _span_var.get()
    return ident, parent, _span_var.set(ident), _open_annotation(name, args, parent)


def _close_span(token, ann) -> None:
    _close_annotation(ann)
    if token is not None:
        _reset(_span_var, token)


@contextmanager
def stage(
    name: str,
    nbytes: int = 0,
    record_span: bool = True,
    args: dict | None = None,
):
    """Time a pipeline stage: aggregates into stages[name] AND records a
    span (no-op without an active trace), with optional `args` on the span
    (which chunk it serves), and holds a "pqt:<name>" profiler annotation
    open for the same interval. record_span=False keeps the aggregate but
    skips the span event and the annotation — for per-ROW micro-stages (the
    assembled-rows loop) that would otherwise flood the event budget with
    sub-microsecond spans and crowd out the meaningful hierarchy. A stage
    opened while another stage aggregate is already open commits its
    seconds as nested (sub-clocked): its wall time is part of the parent's
    and rollup TOTALs count it once."""
    t = _active_var.get()
    if t is None:
        yield
        return
    token, nested = _enter_stage()
    ident, parent, span_token, ann = (
        _open_span(t, name, args) if record_span else _NO_SPAN
    )
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        dt = time.perf_counter_ns() - t0
        _close_span(span_token, ann)
        _reset(_stage_depth_var, token)
        t._commit(
            name,
            dt / 1e9,
            nbytes,
            1,
            start_ns=t0 if record_span else None,
            dur_ns=dt,
            args=args if record_span else None,
            nested=nested,
            ident=ident,
            parent=parent,
        )


class _Elapsed:
    """Result holder for timed_stage(): .seconds is valid after the block."""

    __slots__ = ("seconds",)

    def __init__(self):
        self.seconds = 0.0


@contextmanager
def timed_stage(name: str, nbytes: int = 0, record_span: bool = True):
    """Like stage(), but ALWAYS measures: yields a holder whose `.seconds`
    is the block's wall time even when no trace is active. For callers that
    feed an always-on metric (e.g. the dataset's wait-time histogram) from
    the same clock read that bills the trace stage — one perf_counter pair,
    two consumers, no skew between what the trace and the registry report."""
    t = _active_var.get()
    out = _Elapsed()
    token, nested = (None, False) if t is None else _enter_stage()
    ident, parent, span_token, ann = (
        _open_span(t, name, None) if t is not None and record_span else _NO_SPAN
    )
    t0 = time.perf_counter_ns()
    try:
        yield out
    finally:
        dt = time.perf_counter_ns() - t0
        out.seconds = dt / 1e9
        if t is not None:
            _close_span(span_token, ann)
            _reset(_stage_depth_var, token)
            t._commit(
                name,
                out.seconds,
                nbytes,
                1,
                start_ns=t0 if record_span else None,
                dur_ns=dt,
                nested=nested,
                ident=ident,
                parent=parent,
            )


@contextmanager
def span(name: str, args: dict | None = None):
    """Pure hierarchy span (file / row_group / chunk levels): records a
    trace event with optional args but does NOT enter the stage aggregates —
    its children (stages) already bill the time, and double-billing would
    corrupt the TOTAL row. Holds a "pqt:<name>" profiler annotation with
    the same args for the same interval."""
    t = _active_var.get()
    if t is None:
        yield
        return
    ident, parent, span_token, ann = _open_span(t, name, args)
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        dur = time.perf_counter_ns() - t0
        _close_span(span_token, ann)
        t._commit(
            name, start_ns=t0, dur_ns=dur, args=args, ident=ident, parent=parent
        )


def active() -> bool:
    """True while a decode_trace() is collecting in this context — callers
    use this to skip instrumentation work (e.g. native per-stage clocks)
    when nobody listens."""
    return _active_var.get() is not None


def current() -> "DecodeTrace | None":
    """The trace active in this context, or None."""
    return _active_var.get()


def add_bytes(name: str, nbytes: int) -> None:
    t = _active_var.get()
    if t is not None:
        t._commit(name, 0.0, nbytes, 0)


def add_seconds(
    name: str, seconds: float, nbytes: int = 0, record_span: bool = True
) -> None:
    """Credit externally-measured wall time to a stage. The span is placed
    ending 'now' (the measurement must have just finished);
    record_span=False credits the aggregate alone — for time that was not
    spent on this thread (a task's wait in a pool's queue, during which the
    worker ran the task before it: a span would overlap that task's on the
    worker's lane). When a stage aggregate is open in this context, the
    credited time is part of that stage's wall and commits as nested
    (counted once in TOTALs)."""
    t = _active_var.get()
    if t is not None:
        dur = int(seconds * 1e9)
        t._commit(
            name,
            seconds,
            nbytes,
            1,
            start_ns=time.perf_counter_ns() - dur if record_span else None,
            dur_ns=dur,
            nested=_stage_depth_var.get() > 0,
        )


def add_seconds_batch(pairs) -> None:
    """Credit a list of (name, seconds) sub-stage clocks that together just
    finished (how the fused native chunk walk reports its internal
    decompress/levels/prescan/copy/crc split). Spans are laid back-to-back
    ENDING now, so they nest inside the enclosing span (their sum never
    exceeds the native call's wall time). Like add_seconds, the batch
    commits as nested when an enclosing stage aggregate is open — the
    sub-clocks are a BREAKDOWN of their parent, not additional wall."""
    t = _active_var.get()
    if t is None:
        return
    nested = _stage_depth_var.get() > 0
    pairs = [(n, s) for n, s in pairs if s > 0]
    cursor = time.perf_counter_ns() - sum(int(s * 1e9) for _, s in pairs)
    for name, sec in pairs:
        dur = int(sec * 1e9)
        t._commit(name, sec, 0, 1, start_ns=cursor, dur_ns=dur, nested=nested)
        cursor += dur


def bump(name: str, nbytes: int = 0) -> None:
    """Count an event (with optional byte volume) under an active trace —
    how tests pin down that an opportunistic path actually engaged. Always
    dual-reports into the process-wide metrics registry (metrics.event), so
    the count survives outside any trace."""
    _metrics.event(name)
    t = _active_var.get()
    if t is not None:
        t._commit(name, 0.0, nbytes, 1)


def count(name: str, n: int = 1) -> None:
    """Count an event under the active trace ONLY — no registry write.
    For call sites that already feed a dedicated always-on counter (the
    block cache's io_cache_hits_total) and need just the per-request
    attribution: one contextvar read when no trace is active, no extra
    lock traffic on hot paths."""
    t = _active_var.get()
    if t is not None:
        t._commit(name, 0.0, 0, n)


def span_allocations() -> int:
    """Process-wide span-event allocation count — the zero-overhead oracle:
    reads with no active trace must not move it."""
    return _span_allocs


def annotation_allocations() -> int:
    """Process-wide count of profiler annotations built — the same oracle:
    reads with no active trace, record_span=False stages and the add_seconds
    sub-clocks must not move it."""
    return _annotation_allocs


def name_os_thread() -> None:
    """Give the calling OS thread its Python thread's name (a pool's
    `initializer`). The profiler names a trace's host lines after the OS
    thread, which Python leaves at the process's name: without this every
    pqt-host_* / pqt-dispatch_* lane of a .xplane.pb reads "python". Linux
    only (prctl PR_SET_NAME, 15 characters); anywhere else a no-op."""
    import ctypes

    name = threading.current_thread().name.encode()[:15]
    try:
        ctypes.CDLL(None).prctl(15, name, 0, 0, 0)
    except (OSError, AttributeError):
        pass


@contextmanager
def jax_profile(logdir: str):
    """Capture a JAX/XLA profiler trace of the enclosed block TOGETHER with
    a decode_trace (yielded): the written .xplane.pb holds the device's ops
    and the program's pqt:* spans on one clock, and the yielded trace the
    per-stage aggregates. The operator's one entry: this is the only place
    the tracing module imports jax."""
    import jax

    with jax.profiler.trace(logdir), decode_trace() as t:
        yield t
