"""Push-down execution of planned scans on the dedicated pqt-serve pool.

The executor turns a PlannedScan into an incremental byte stream:

  * every unit (one row group of one file) decodes as an independent task
    on the process-wide bounded `pqt-serve` pool (PQT_SERVE_THREADS) —
    separate from the chunk-prepare / pqt-data / pqt-io pools, so serve
    traffic can never deadlock a dataset loader (and vice versa);
  * results stream back IN PLAN ORDER with a bounded lookahead `window`:
    at most `window` units are in flight or buffered per request, and the
    generator only advances when the consumer (the chunked HTTP write)
    drains — backpressure is the pull itself, nothing buffers the whole
    result;
  * predicate push-down continues below the plan's group pruning: each
    unit reads through the reader's page-index pruning + exact residual
    filtering (core/filter.py), with the projection applied at the source
    (only selected chunks' byte ranges are fetched, through the shared
    BlockCache);
  * cancellation is cooperative: the deadline and the abort flag are
    checked between units and every few thousand rows inside one, and
    result waits are bounded by the deadline — an expired or disconnected
    request frees its slot promptly instead of scanning to the end.

Output formats: "jsonl" (rows exactly as `parquet-tool cat` prints them,
one chunk per unit) and "arrow-ipc" (one Arrow IPC stream; each unit's
table appended as record batches, EOS on completion).
"""

from __future__ import annotations

import json
import os
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutTimeout

from ..core.reader import PARQUET_ERRORS, FileReader
from ..io.source import SourceError
from ..obs.cost import unit_clock
from ..obs.pool import instrumented_submit
from ..utils import metrics as _metrics
from ..utils.trace import span, stage
from .protocol import ServeError, json_default

__all__ = ["serve_pool", "execute_stream", "execute_query"]

_ROW_CHECK_EVERY = 4096  # rows between cooperative cancellation checks
_WAIT_SLICE_S = 0.1  # result-wait poll granularity (bounds deadline latency)

_pool = None
_pool_size = 0
_pool_lock = threading.Lock()


def serve_pool() -> ThreadPoolExecutor:
    """The process-wide scan-execution pool. Sized by PQT_SERVE_THREADS
    (default: min(8, cpus)); dedicated so nested pools (chunk prepare,
    pqt-io readahead) can never self-deadlock against serve traffic."""
    global _pool, _pool_size
    with _pool_lock:
        if _pool is None:
            n = int(
                os.environ.get("PQT_SERVE_THREADS", min(8, os.cpu_count() or 4))
            )
            _pool_size = max(1, n)
            _pool = ThreadPoolExecutor(
                max_workers=_pool_size, thread_name_prefix="pqt-serve"
            )
        return _pool


def pool_size() -> int:
    """The serve pool's worker count (creating the pool if needed)."""
    serve_pool()
    return _pool_size


class _Check:
    """The cooperative cancellation point: deadline + abort flag in one
    callable, shared by the request generator and its unit tasks."""

    __slots__ = ("deadline", "abort")

    def __init__(self, deadline=None):
        self.deadline = deadline
        self.abort = threading.Event()

    def __call__(self) -> None:
        if self.abort.is_set():
            raise ServeError(
                499, "cancelled", "request cancelled (client gone or drained)"
            )
        if self.deadline is not None:
            self.deadline.check()

    def wait_slice(self) -> float:
        if self.deadline is None:
            return _WAIT_SLICE_S
        rem = self.deadline.remaining()
        if rem is None:
            return _WAIT_SLICE_S
        return max(0.0, min(_WAIT_SLICE_S, rem))


def _open_reader(session, planned, unit) -> FileReader:
    """One unit's FileReader, as the serve.open_reader stage (one call a
    unit), nested in serve.execute / serve.aggregate."""
    meta = planned.plan.metas[unit.file_index]
    with stage("serve.open_reader", args={"group": unit.row_group}):
        return FileReader(
            session.open_source(unit.path),
            columns=planned.request.columns,
            metadata=meta,
            block_cache=session.block_cache,
            coalesce_gap=getattr(session, "coalesce_gap", None),
        )


def _close_unit_reader(session, reader) -> None:
    # factory-built sources (chaos/remote seam) are caller-owned per the
    # ByteSource contract: the reader won't close them, so we must.
    # A span under the opening stage's name: the close is in the trace (an
    # idle gap of the device can be put down to it) and the stage's calls
    # stay one a unit
    with span("serve.open_reader"):
        reader.close()
        if session.source_factory is not None:
            reader._source.close()


def _run_jsonl_unit(session, planned, unit, max_rows, check):
    """Decode + serialize one unit; returns (payload bytes, rows).
    unit_clock bills the unit's thread-time (exact per-thread CPU) to the
    request's tenant through the cost contextvar the submit carried."""
    check()
    with unit_clock(), stage("serve.execute"):
        reader = _open_reader(session, planned, unit)
        try:
            lines = []
            n = 0
            for row in reader.iter_rows(
                row_groups=[unit.row_group], filters=planned.request.filters
            ):
                lines.append(json.dumps(row, default=json_default))
                n += 1
                if n % _ROW_CHECK_EVERY == 0:
                    check()
                if max_rows is not None and n >= max_rows:
                    break
            payload = ("\n".join(lines) + "\n").encode() if lines else b""
            return payload, n
        finally:
            _close_unit_reader(session, reader)


def _run_arrow_unit(session, planned, unit, max_rows, check):
    """Decode one unit to a pyarrow Table (serialized by the stream side,
    which owns the single IPC writer). unit_clock: see _run_jsonl_unit."""
    check()
    with unit_clock(), stage("serve.execute"):
        reader = _open_reader(session, planned, unit)
        try:
            t = reader.to_arrow(
                row_groups=[unit.row_group], filters=planned.request.filters
            )
            if max_rows is not None and t.num_rows > max_rows:
                t = t.slice(0, max_rows)
            return t
        finally:
            _close_unit_reader(session, reader)


def _pipelined(units, run_one, window: int, check: "_Check", ahead: int = 0):
    """Bounded in-order unit pipeline: up to `window` units running, results
    yielded in plan order. `ahead` more results may be held finished while
    the oldest unit still runs (a query's kilobyte partials; a streamed scan
    holds payload and passes 0). The next unit is submitted by whoever frees
    its slot — the worker that finished a unit, or this thread when it takes
    a result — so a worker never idles behind the oldest unit or behind this
    thread's waking up, and nothing is parked in the pool's queue, whose wait
    and depth are what brownout reads. Result waits poll in deadline-bounded
    slices so an expired request raises its typed 504 even while a unit is
    stuck."""
    pending: deque = deque()  # submitted, not yet yielded, in plan order
    lock = threading.Lock()
    idx = running = 0
    closed = False

    def refill() -> None:
        nonlocal idx, running
        with lock:
            while (
                not closed
                and idx < len(units)
                and running < window
                and len(pending) < window + ahead
            ):
                pending.append(
                    instrumented_submit(
                        serve_pool(), run_and_refill, units[idx], pool="pqt-serve"
                    )
                )
                idx += 1
                running += 1

    def run_and_refill(u):
        nonlocal running
        try:
            return run_one(u)
        finally:
            with lock:
                running -= 1
            refill()

    try:
        refill()
        while pending:
            fut = pending[0]
            while True:
                check()
                try:
                    result = fut.result(timeout=check.wait_slice())
                    break
                except _FutTimeout:
                    continue
            with lock:
                pending.popleft()
            refill()
            yield result
    finally:
        # abort first so already-running tasks exit at their next check,
        # then drop anything still queued
        with lock:
            closed = True
        check.abort.set()
        for f in pending:
            f.cancel()


def _wrap_decode_errors(gen):
    """Typed-error discipline at the execution boundary: a corrupt file
    surfaces as a ServeError (422) the server renders structurally, never
    a raw decode exception unwinding the handler. A circuit breaker's
    fast-fail (SourceError code="breaker_open" — the source is KNOWN dark)
    becomes a 503 with Retry-After instead: the file is fine, the
    transport is down, and the client should back off rather than re-ask —
    and the unit fails in microseconds instead of burning its deadline on
    a retry ladder that cannot succeed. Counted
    serve_shed_total{reason="breaker_open"}."""
    try:
        yield from gen
    except ServeError:
        raise
    except SourceError as e:
        code = getattr(e, "code", None)
        if code == "breaker_open":
            _metrics.inc("serve_shed_total", reason="breaker_open")
            raise ServeError(
                503, "source_unavailable",
                f"source circuit breaker open: {e}", retry_after_s=1,
            ) from None
        if code == "retry_exhausted":
            # the ladder gave up on a TRANSIENT fault storm: the file is
            # not wrong, the transport is — same 503 + Retry-After shape
            # the raw OSError below gets, not a permanent-looking 422
            raise ServeError(
                503, "source_error", f"{type(e).__name__}: {e}",
                retry_after_s=1,
            ) from None
        raise ServeError(
            422, "unreadable_file", f"{type(e).__name__}: {e}"
        ) from None
    except PARQUET_ERRORS as e:
        raise ServeError(
            422, "unreadable_file", f"{type(e).__name__}: {e}"
        ) from None
    except OSError as e:
        # a raw transport fault (EIO from a flaky store, a vanished mount)
        # is the DAEMON's environment failing, not the request: 503 +
        # Retry-After, not a 500 that reads as a server bug
        raise ServeError(
            503, "source_error", f"{type(e).__name__}: {e}", retry_after_s=1
        ) from None


def _count_bytes(payload: bytes) -> None:
    _metrics.inc("serve_scan_bytes_total", len(payload))


def _stream_jsonl(planned, session, check, window):
    remaining = planned.request.limit
    units = planned.units

    def run(u, cap=None):
        return _run_jsonl_unit(session, planned, u, cap, check)

    if remaining is None:
        for payload, _n in _pipelined(units, run, window, check):
            if payload:
                _count_bytes(payload)
                yield payload
        return
    # limited scans run sequentially: each unit's cap is what's left, and
    # lookahead past a satisfied limit would be wasted decode work
    for u in units:
        if remaining <= 0:
            break
        check()
        fut = instrumented_submit(
            serve_pool(), run, u, remaining, pool="pqt-serve"
        )
        while True:
            check()
            try:
                payload, n = fut.result(timeout=check.wait_slice())
                break
            except _FutTimeout:
                continue
        remaining -= n
        if payload:
            _count_bytes(payload)
            yield payload


class _ChunkSink:
    """A file-like the single Arrow IPC writer writes into; `take()` hands
    the bytes accumulated since the last take to the HTTP stream."""

    closed = False  # pyarrow's IPC writer checks the file-like protocol

    def __init__(self):
        self._parts: list[bytes] = []

    def write(self, data) -> int:
        b = bytes(data)
        self._parts.append(b)
        return len(b)

    def flush(self) -> None:
        pass

    def take(self) -> bytes:
        out = b"".join(self._parts)
        self._parts.clear()
        return out


def _empty_table(planned, session):
    """A zero-row table carrying the scan's schema (so an empty result is
    still a VALID Arrow IPC stream: schema header + EOS)."""
    for fi, meta in enumerate(planned.plan.metas):
        if meta is None:
            continue
        reader = FileReader(
            session.open_source(planned.plan.files[fi]),
            columns=planned.request.columns,
            metadata=meta,
        )
        try:
            return reader.to_arrow(row_groups=[])
        finally:
            _close_unit_reader(session, reader)
    raise ServeError(422, "unreadable_file", "no readable file to derive a schema")


def _stream_arrow(planned, session, check, window):
    import pyarrow as pa

    sink = _ChunkSink()
    writer = None
    remaining = planned.request.limit
    units = planned.units

    def run(u):
        return _run_arrow_unit(session, planned, u, None, check)

    def limited():
        # limited scans run sequentially, each unit capped at what the
        # limit STILL needs (`remaining` shrinks as the loop consumes) —
        # lookahead past a satisfied limit would be wasted decode work
        for u in units:
            if remaining <= 0:
                return
            check()
            fut = instrumented_submit(
                serve_pool(), _run_arrow_unit, session, planned, u,
                remaining, check, pool="pqt-serve",
            )
            while True:
                check()
                try:
                    yield fut.result(timeout=check.wait_slice())
                    break
                except _FutTimeout:
                    continue

    try:
        source = (
            _pipelined(units, run, window, check)
            if remaining is None
            else limited()
        )
        for table in source:
            if remaining is not None:
                table = table.slice(0, remaining)
                remaining -= table.num_rows
            if writer is None:
                writer = pa.ipc.new_stream(sink, table.schema)
            try:
                writer.write_table(table)
            except pa.ArrowInvalid as e:
                raise ServeError(
                    422, "schema_mismatch",
                    f"files in one scan must share a schema: {e}",
                ) from None
            payload = sink.take()
            if payload:
                _count_bytes(payload)
                yield payload
            if remaining is not None and remaining <= 0:
                break
        if writer is None:
            writer = pa.ipc.new_stream(sink, _empty_table(planned, session).schema)
        writer.close()
        payload = sink.take()
        if payload:
            _count_bytes(payload)
            yield payload
    finally:
        check.abort.set()


def execute_query(
    planned, query, session, *, deadline=None, window: int = 2, device=None
):
    """Aggregation push-down over the planned units (POST /v1/query).

    Each unit decodes + filters + partially aggregates as one pqt-serve
    pool task (the residual filter runs the vectorized mask pipeline via
    to_arrow's buffer-level take); partials merge on the caller's thread
    with exact pyarrow semantics (serve/aggregate.py), bounded by the
    request's max_groups. Pure count(*) with no filters never opens a
    file — the footer-promised unit row counts ARE the answer. Returns the
    response body dict; every failure mode is a typed ServeError, and the
    deadline/abort checks run between units exactly like streamed scans.

    `device` (ServeConfig(device=...)) attaches an accelerator backend:
    each unit first tries the device-resident path (serve/query_device —
    decode into HBM, resident residual mask, one masked reduction per
    aggregate, expressions and integer-backed DECIMAL / DATE leaves
    included, one fetch of the unit's scalars) and falls back, typed and
    counted
    (query_device_units_total{engine=...}), to the host vec engine for any
    shape outside the device envelope. True means the process-default jax
    device; a jax.Device pins one."""
    from .aggregate import (
        QueryState,
        query_columns,
        result_dict,
        unit_count_partial,
        unit_partial,
    )

    check = _Check(deadline)
    if window < 1:
        raise ValueError("executor: window must be >= 1")
    cols = query_columns(query)
    decode = bool(cols) or query.filters is not None
    state = QueryState(query)
    units = planned.units
    device_unit = None
    if device is not None and decode:
        try:
            from .query_device import DeviceQueryError, device_unit_partial

            device_unit = device_unit_partial
        except ImportError:
            # jax-less deployment with device= set: every unit is a host
            # unit; the counter makes the misconfiguration visible
            _metrics.inc("query_device_unavailable_total")
            device_unit = None
    # a streamed scan's window bounds BUFFERED payload; a query's unit
    # results are kilobyte partials, so the pool's worth of units run at once
    # — idle workers are pure waste — and as many finished partials again
    # may wait their turn to be absorbed in plan order (the order is part of
    # the answer: a float sum's last bit)
    window = max(window, min(pool_size(), len(units) or 1))

    def run(u):
        check()
        if not decode:
            return (
                unit_count_partial(query, u.num_rows), u.num_rows, u.num_rows
            )
        with unit_clock(), stage("serve.aggregate"):
            reader = _open_reader(session, planned, u)
            try:
                if device_unit is not None:
                    try:
                        part = device_unit(
                            reader,
                            u.row_group,
                            query,
                            planned.request.filters,
                            None if device is True else device,
                        )
                        _metrics.inc(
                            "query_device_units_total", engine="device"
                        )
                        return part
                    except DeviceQueryError:
                        _metrics.inc(
                            "query_device_units_total", engine="host_fallback"
                        )
                t = reader.to_arrow(
                    row_groups=[u.row_group], filters=planned.request.filters
                )
            finally:
                _close_unit_reader(session, reader)
            return (unit_partial(t, query), u.num_rows, t.num_rows)

    gen = _pipelined(units, run, window, check, ahead=window)
    try:
        for part in _wrap_decode_errors(gen):
            with stage("serve.merge"):
                state.absorb(part)
    finally:
        gen.close()
    _metrics.inc("serve_aggregate_requests_total")
    return result_dict(query, state, units=len(units))


def execute_stream(planned, session, *, deadline=None, window: int = 2):
    """The request's payload-chunk generator. Pull-driven: nothing decodes
    beyond `window` units ahead of what the consumer has taken, and closing
    the generator (client disconnect) aborts in-flight unit tasks at their
    next cooperative check. Raises ServeError (typed) for every failure
    mode — deadline, cancellation, corrupt data, schema drift."""
    check = _Check(deadline)
    if window < 1:
        raise ValueError("executor: window must be >= 1")
    if planned.request.format == "arrow-ipc":
        gen = _stream_arrow(planned, session, check, window)
    else:
        gen = _stream_jsonl(planned, session, check, window)

    def outer():
        try:
            for payload in _wrap_decode_errors(gen):
                # the stage brackets the YIELD: its wall time is how long
                # the consumer (the chunked HTTP write) took to drain this
                # chunk — the backpressure/writeback measurement
                with stage("serve.stream", nbytes=len(payload)):
                    yield payload
        finally:
            check.abort.set()
            gen.close()

    return outer()
