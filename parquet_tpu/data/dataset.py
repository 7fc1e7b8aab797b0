"""ParquetDataset: sharded, prefetching, checkpointable streaming batches.

The scheduler/runtime layer on top of the decode core — what a training or
bulk-inference job actually consumes. Every consumer used to hand-roll a
loop over `FileReader.read_row_group` on one file; this subsystem gives the
multi-file, multi-host, overlap-I/O-with-compute path:

    ds = ParquetDataset("shard-*.parquet", columns=["x", "y"],
                        batch_size=4096, shuffle=True, seed=7,
                        prefetch=2, on_error="skip")
    for batch in ds:                      # {leaf path: np.ndarray[4096, ...]}
        step(batch)

Semantics, in the order the pipeline applies them:

  plan      footers parse lazily (once per file); one work unit per
            (file, row group); `filters` prune units through the
            statistics/bloom path before any data page is read.
            `filter_rows=True` additionally masks INDIVIDUAL rows inside
            surviving groups with the vectorized filter engine (null mode
            "row": a null fails every value predicate), so batches hold
            only matching rows — the read set silently extends to cover
            filter-referenced columns, which are dropped again before
            delivery unless projected.
  shard     the epoch's unit order is a pure function of (seed, epoch),
            computed identically on every host, then striped over
            `shard_count * worker_count` slots — each unit visited by
            exactly one (process, worker) per epoch.
  prefetch  a bounded pool ("pqt-data" threads) decodes units k+1..k+depth
            while the consumer works on k's batches; depth 0 = fully
            synchronous. Wait time is always measured (dataset_wait_seconds
            histogram + dataset.wait trace stage): a starved loop is
            visible, not mysterious.
  rebatch   decoded row groups re-slice into fixed `batch_size` batches,
            remainders carrying ACROSS unit boundaries; the epoch tail
            follows `remainder=` ("drop" | "keep" | "pad").
  deliver   host numpy dicts by default; `device=` (a jax.Device or a
            Sharding) double-buffers `jax.device_put` so batch k+1's upload
            overlaps the consumer's step on k.
  resume    iter(ds) -> DatasetIterator with state_dict()/load_state_dict():
            (epoch, unit cursor, intra-unit row offset) — a resumed
            iterator reproduces the remaining batch stream byte-identically,
            mid-epoch, under sharding and shuffling.

Corruption follows FileReader's on_error policy per unit: with "skip" a
corrupt row group (or a file with an unreadable footer) drops with a counter
(dataset_units_skipped / dataset_files_skipped) and every clean unit still
arrives exactly once; "null" substitutes nulls where the schema allows
(pair it with nullable="zero"). Device-resident training jobs that would
rather die than silently lose rows keep the default "raise".
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..core.arrays import ByteArrayData
from ..core.reader import PARQUET_ERRORS, FileReader
from ..meta.file_meta import ParquetFileError
from ..obs.log import log_event
from ..obs.pool import instrumented_submit
from ..obs.recorder import recorder as _recorder
from ..utils import metrics as _metrics
from ..utils.trace import bump, span, timed_stage
from .plan import ScanPlan, build_plan

__all__ = ["ParquetDataset", "DatasetIterator"]

_STATE_VERSION = 1

# The prefetch queue-depth gauge is process-wide (one Prometheus sample),
# while iterators are many and concurrent — each tracks its own delta here
# so the exposed value is the TOTAL in-flight unit count, not whichever
# iterator wrote last (a finishing iterator must not zero a live one's
# starvation signal).
# (re-entrant: _fetch_units' `finally` lands here from a garbage collection
# that may start while this very thread holds the lock, see utils/metrics.py)
_inflight_lock = threading.RLock()
_inflight_units = 0


def _inflight_add(n: int) -> None:
    global _inflight_units
    with _inflight_lock:
        _inflight_units += n
        _metrics.set_gauge("dataset_prefetch_depth", _inflight_units)


class ParquetDataset:
    """A multi-file Parquet scan shaped for training loops.

    Construction is cheap: footers parse on first use (iteration, or any
    plan-derived property). Iterating yields {leaf path tuple: np.ndarray}
    batches of exactly `batch_size` rows (tail per `remainder=`); with
    `device=` the arrays are device-resident jax arrays instead.
    """

    def __init__(
        self,
        paths_or_glob,
        *,
        batch_size: int,
        columns=None,
        filters=None,
        filter_rows: bool = False,
        shuffle: bool = False,
        seed: int = 0,
        num_epochs: int | None = 1,
        prefetch: int = 2,
        remainder: str = "drop",
        shard=None,
        worker=None,
        on_error: str = "raise",
        nullable: str = "error",
        validate_crc: bool = False,
        device=None,
        cache_bytes: int = 0,
        cache_disk_bytes: int = 0,
        cache_dir=None,
        block_cache=None,
        readahead_bytes: int | None = None,
        io_autotune: bool = False,
        slo_wait_ms: float | None = None,
        controller=None,
    ):
        if batch_size <= 0:
            raise ValueError("dataset: batch_size must be positive")
        if remainder not in ("drop", "keep", "pad"):
            raise ValueError(
                f'dataset: remainder must be "drop", "keep" or "pad", '
                f"got {remainder!r}"
            )
        if on_error not in ("raise", "skip", "null"):
            raise ValueError(
                f'dataset: on_error must be "raise", "skip" or "null", '
                f"got {on_error!r}"
            )
        if nullable not in ("error", "zero"):
            raise ValueError(
                f'dataset: nullable must be "error" or "zero", got {nullable!r}'
            )
        if on_error == "null" and nullable != "zero":
            raise ValueError(
                'dataset: on_error="null" delivers nulled chunks, which need '
                'nullable="zero" to batch'
            )
        if filter_rows and filters is None:
            raise ValueError("dataset: filter_rows=True requires filters")
        if num_epochs is not None and num_epochs < 0:
            raise ValueError("dataset: num_epochs must be >= 0 or None")
        if prefetch < 0:
            raise ValueError("dataset: prefetch depth must be >= 0")
        if cache_bytes < 0:
            raise ValueError("dataset: cache_bytes must be >= 0")
        if cache_disk_bytes < 0:
            raise ValueError("dataset: cache_disk_bytes must be >= 0")
        self.paths_or_glob = paths_or_glob
        self.batch_size = int(batch_size)
        self.columns = list(columns) if columns is not None else None
        self.filters = filters
        self.filter_rows = bool(filter_rows)
        self.shuffle = bool(shuffle)
        self.seed = int(seed)
        self.num_epochs = num_epochs
        self.prefetch = int(prefetch)
        self.remainder = remainder
        self.on_error = on_error
        self.nullable = nullable
        self.validate_crc = bool(validate_crc)
        self.device = device
        si, sc = self._resolve_split(shard, "shard")
        wi, wc = self._resolve_split(worker, "worker")
        # one flat slot space: process-major, worker-minor — host p's worker
        # w owns stripe p*wc + w of sc*wc
        self.shard_index = si * wc + wi
        self.shard_count = sc * wc
        self._plan: ScanPlan | None = None
        self._plan_lock = threading.Lock()
        self._pool: ThreadPoolExecutor | None = None
        self._closed = False
        # IO layer: footers cache process-wide (validated per generation by
        # size+mtime for paths, size+ETag for URLs, so it is always safe);
        # cache_bytes > 0 adds a shared byte-budgeted block cache — unit
        # decodes read through it, repeat epochs hit memory, and the pqt-io
        # readahead scheduler streams the NEXT units' planned byte ranges
        # into it while pqt-data decodes the current window
        # (readahead_bytes bounds its in-flight budget, default =
        # cache_bytes / 4). cache_disk_bytes > 0 grows the block cache
        # into a RAM -> disk TieredCache spilling to cache_dir (a private
        # temp dir when None) — the remote-corpus shape, where the hot set
        # outlives RAM but a local disk beats the store by ~100x.
        # block_cache= passes a PRE-BUILT cache (BlockCache or
        # TieredCache, caller-owned) so co-resident consumers — a serve
        # daemon and its training loaders — pool ONE tier budget.
        # io_autotune=True resolves the coalesce gap per fetch (and
        # deepens the readahead budget) from the observed per-transport
        # latency profile (io/autotune.py): local corpora keep the 64 KiB
        # default, remote ones coalesce MiB-scale.
        from ..io.cache import BlockCache, shared_footer_cache
        from ..io.planner import Readahead
        from ..io.tiercache import TieredCache

        self._footer_cache = shared_footer_cache()
        self.io_autotune = bool(io_autotune)
        self._owns_cache = block_cache is None
        if block_cache is not None:
            self._block_cache = block_cache
        elif cache_disk_bytes:
            self._block_cache = TieredCache(
                ram_bytes=cache_bytes or (64 << 20),
                disk_bytes=cache_disk_bytes,
                cache_dir=cache_dir,
            )
        elif cache_bytes:
            self._block_cache = BlockCache(cache_bytes)
        else:
            self._block_cache = None
        self._readahead = (
            Readahead(
                self._block_cache,
                budget_bytes=(
                    readahead_bytes
                    if readahead_bytes is not None
                    else max(cache_bytes // 4, 1 << 20)
                ),
                autotune=self.io_autotune,
            )
            if self._block_cache is not None
            else None
        )
        # per-file parsed Schema cache: _load_unit opens one reader PER ROW
        # GROUP, and rebuilding the schema tree from thrift every unit is
        # pure waste when the footer is already cached on the plan
        self._schemas: dict[int, object] = {}
        # elastic SLO: slo_wait_ms attaches an AIMD controller that scales
        # prefetch depth / pqt-data workers / the readahead budget to keep
        # consumer waits under the SLO. Advisory only — it never touches
        # anything state_dict() depends on, so resume stays byte-identical.
        # A pre-built AIMDController (controller=) wins, letting tests
        # inject clocks and registries.
        if controller is not None:
            self._controller = controller
        elif slo_wait_ms is not None:
            from .controller import AIMDController

            self._controller = AIMDController(
                slo_wait_ms=slo_wait_ms,
                initial_depth=max(1, self.prefetch),
                max_depth=max(32, self.prefetch),
            )
        else:
            self._controller = None

    @staticmethod
    def _resolve_split(spec, what: str) -> tuple[int, int]:
        if spec is None:
            return 0, 1
        if spec == "jax":
            if what != "shard":
                # worker="jax" would square the process stripe into a
                # diagonal — (P-1)/P of all units visited by nobody
                raise ValueError(
                    'dataset: only shard= accepts "jax"; worker= is the '
                    "per-host sub-split and needs an explicit (index, count)"
                )
            # opt-in only: importing jax initializes the backend, which a
            # pure host data loader must never do implicitly
            import jax

            return jax.process_index(), jax.process_count()
        i, n = spec
        i, n = int(i), int(n)
        if n <= 0 or not 0 <= i < n:
            raise ValueError(f"dataset: bad {what} split ({i}, {n})")
        return i, n

    # -- plan ------------------------------------------------------------------

    @property
    def plan(self) -> ScanPlan:
        """The global unit plan (footers parse on first access)."""
        with self._plan_lock:
            if self._plan is None:
                plan = build_plan(
                    self.paths_or_glob,
                    filters=self.filters,
                    on_error=self.on_error,
                    footer_cache=self._footer_cache,
                )
                # Validate the projection ONCE against the first readable
                # schema, outside the skip policy: a misspelled columns=
                # entry is a configuration error — under on_error="skip" it
                # would otherwise quarantine every unit and deliver an
                # empty dataset with no error.
                if self.columns is not None:
                    for fi, meta in enumerate(plan.metas):
                        if meta is not None:
                            with FileReader(
                                plan.files[fi], columns=self.columns,
                                metadata=meta,
                            ):
                                pass
                            break
                self._plan = plan
            return self._plan

    def _selected_leaf_paths(self, file_index: int):
        """The projection as leaf path tuples for one plan file (None = all
        columns) — what the io planner needs to compute a unit's exact byte
        ranges for readahead. Best-effort: resolution failures return None
        (readahead fetches everything; decode still raises the precise
        error)."""
        if self.columns is None:
            return None
        try:
            schema = self._file_schema(file_index)
        except Exception:  # noqa: BLE001 — advisory path only
            return None
        selected = set()
        for c in self.columns:
            path = tuple(c.split(".")) if isinstance(c, str) else tuple(c)
            selected.update(
                leaf.path
                for leaf in schema.leaves
                if leaf.path[: len(path)] == path
            )
        return selected or None

    def _unit_ranges(self, unit) -> list:
        """The planned (offset, length) byte ranges of one unit under the
        dataset's projection (readahead's shopping list)."""
        from ..io.planner import plan_ranges

        meta = self.plan.metas[unit.file_index]
        if meta is None:
            return []
        return plan_ranges(
            meta,
            row_groups=[unit.row_group],
            columns=self._selected_leaf_paths(unit.file_index),
        )

    def _file_schema(self, file_index: int):
        """The parsed Schema of one plan file (cached; footers come from
        the plan, so each file's schema tree builds exactly once no matter
        how many row groups stream from it)."""
        s = self._schemas.get(file_index)
        if s is None:
            from ..core.schema import Schema

            s = Schema.from_thrift(self.plan.metas[file_index].schema)
            # benign race: two workers may build the same schema; last
            # write wins and both values are equivalent
            self._schemas[file_index] = s
        return s

    @property
    def total_rows(self) -> int:
        """Rows the footers promise across ALL shards (before any on_error
        skipping at decode time)."""
        return self.plan.total_rows

    def epoch_order(self, epoch: int) -> list[int]:
        """This shard's unit visit order for `epoch` (plan unit indices)."""
        return self.plan.epoch_order(
            epoch,
            seed=self.seed,
            shuffle=self.shuffle,
            shard_index=self.shard_index,
            shard_count=self.shard_count,
        )

    # -- prefetch pool ---------------------------------------------------------

    def _worker_pool(self) -> ThreadPoolExecutor:
        """The dataset's own bounded decode pool ("pqt-data", sized
        min(prefetch, PQT_DATA_THREADS or cpu)). Deliberately SEPARATE from
        the chunk-prepare pool: unit-level tasks that internally fan out
        chunk work into the same pool they run in would deadlock once the
        pool saturates."""
        with self._plan_lock:
            if self._closed:
                raise RuntimeError("dataset: closed")
            if self._pool is None:
                env = os.environ.get("PQT_DATA_THREADS")
                cap = int(env) if env else (os.cpu_count() or 1)
                if self._controller is not None:
                    workers = self._controller.worker_target
                else:
                    workers = max(1, min(self.prefetch, cap))
                self._pool = ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix="pqt-data"
                )
            return self._pool

    def _apply_controller_targets(self) -> None:
        """Push the SLO controller's current targets onto the pool and the
        readahead scheduler (called from the fetch loop after each control
        tick). Worker growth takes effect on the next submit (the executor
        spawns threads lazily up to _max_workers); shrink is lazy — extra
        idle workers just park, and actual concurrency is already bounded
        by the prefetch window."""
        ctl = self._controller
        if ctl is None:
            return
        pool = self._pool
        if pool is not None:
            w = ctl.worker_target
            # _max_workers is the executor's documented-by-use sizing knob;
            # there is no public resize API in the stdlib
            if w != pool._max_workers:
                pool._max_workers = w
        if self._readahead is not None:
            self._readahead.budget_bytes = ctl.readahead_budget

    def close(self) -> None:
        """Shut the prefetch pool down (idempotent). The dataset and its
        iterators stop being usable: further iteration raises instead of
        silently resurrecting an untracked worker pool. The readahead
        scheduler stops accepting work and cancels queued fetches (running
        ones finish — they touch only the shared cache, never the pools
        being torn down)."""
        with self._plan_lock:
            self._closed = True
            pool, self._pool = self._pool, None
        if self._readahead is not None:
            self._readahead.close()
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
        # a tiered cache the DATASET built owns its spill files; a passed
        # block_cache= belongs to the caller (it may be the daemon's)
        if self._owns_cache and hasattr(self._block_cache, "close"):
            self._block_cache.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- iteration -------------------------------------------------------------

    def __iter__(self) -> "DatasetIterator":
        if self._closed:
            raise RuntimeError("dataset: closed")
        return DatasetIterator(self)

    def iterator(self, state: dict | None = None) -> "DatasetIterator":
        """A fresh iterator, optionally resumed from a state_dict()."""
        it = iter(self)
        if state is not None:
            it.load_state_dict(state)
        return it


class DatasetIterator:
    """One pass (or N epochs) over a ParquetDataset's shard of the plan.

    Checkpointable: state_dict() captures (epoch, unit cursor, intra-unit
    row offset) AS OF THE BATCHES ALREADY DELIVERED — load_state_dict() on a
    fresh iterator reproduces the remaining batch stream byte-identically.
    """

    def __init__(self, dataset: ParquetDataset):
        self._ds = dataset
        self._epoch = 0
        self._pos = 0  # epoch-order position of the next row to deliver
        self._off = 0  # row offset within that unit
        self._exhausted = False
        self._started = False
        self._dtypes: dict | None = None  # cross-file schema consistency
        self._gen = None

    # -- checkpointing ---------------------------------------------------------

    def state_dict(self) -> dict:
        """Resume point covering every batch already delivered."""
        ds = self._ds
        return {
            "version": _STATE_VERSION,
            "epoch": self._epoch,
            "unit_pos": self._pos,
            "row_offset": self._off,
            "exhausted": self._exhausted,
            "seed": ds.seed,
            "shuffle": ds.shuffle,
            "batch_size": ds.batch_size,
            "remainder": ds.remainder,
            "shard": [ds.shard_index, ds.shard_count],
            "plan": ds.plan.fingerprint(),
        }

    def load_state_dict(self, state: dict) -> None:
        """Position this (not-yet-started) iterator at a checkpoint.

        The configuration a cursor's meaning depends on must match: the
        epoch permutation (seed/shuffle), the stripe (shard), the batch
        grid (batch_size/remainder) and the plan itself. Anything else
        (prefetch depth, device, worker pool size) is free to differ —
        it affects speed, never the stream."""
        if self._started:
            raise RuntimeError(
                "dataset: load_state_dict on a started iterator (make a "
                "fresh one)"
            )
        if state.get("version") != _STATE_VERSION:
            raise ValueError(
                f"dataset: unknown checkpoint version {state.get('version')!r}"
            )
        ds = self._ds
        for key, ours in (
            ("seed", ds.seed),
            ("shuffle", ds.shuffle),
            ("batch_size", ds.batch_size),
            ("remainder", ds.remainder),
            ("shard", [ds.shard_index, ds.shard_count]),
            ("plan", ds.plan.fingerprint()),
        ):
            if state.get(key) != ours:
                raise ValueError(
                    f"dataset: checkpoint {key} mismatch "
                    f"({state.get(key)!r} != {ours!r}); the cursor would "
                    "not mean the same stream"
                )
        self._epoch = int(state["epoch"])
        self._pos = int(state["unit_pos"])
        self._off = int(state["row_offset"])
        self._exhausted = bool(state.get("exhausted", False))

    # -- iteration -------------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        if self._gen is None:
            self._started = True
            self._gen = self._stream()
        try:
            batch, state = next(self._gen)
        except StopIteration:
            self._exhausted = True
            raise
        # commit ONLY at delivery: with device put-pipelining, batches ahead
        # of the consumer are in flight — a checkpoint must not cover them
        self._epoch, self._pos, self._off = state
        return batch

    def close(self) -> None:
        """Abandon the iterator: queued (not yet running) prefetch work is
        cancelled; running unit decodes finish and are dropped."""
        gen, self._gen = self._gen, None
        self._exhausted = True
        if gen is not None:
            gen.close()

    # -- internals -------------------------------------------------------------

    def _stream(self):
        """(batch, state-after-batch) pairs, device-put-pipelined when the
        dataset is device-destined."""
        gen = self._batches()
        placement = self._ds.device
        if placement is None:
            yield from gen
            return
        from ..kernels.pipeline import check_double_delivery, device_put_pipelined

        states: deque = deque()

        def host_side():
            for b, s in gen:
                doubles = [
                    ".".join(p) for p, a in b.items() if a.dtype == np.float64
                ]
                if doubles:
                    # a TPU hands float64 back an ulp off: typed refusal,
                    # never an inexact batch (kernels.pipeline.DeviceDoubleError).
                    # The loader takes no other form yet: the two a TPU holds
                    # exactly are FileReader's device entry points' doubles=
                    # ("bits", "float32"); here the batch stays on the host
                    check_double_delivery(doubles, placement)
                states.append(s)  # appended before the yield: stays aligned
                yield b

        for db in device_put_pipelined(
            host_side(), placement=placement, depth=2,
            stage_name="dataset.device_put",
        ):
            yield db, states.popleft()

    def _batches(self):
        ds = self._ds
        B = ds.batch_size
        epoch, pos, off = self._epoch, self._pos, self._off
        while ds.num_epochs is None or epoch < ds.num_epochs:
            order = ds.epoch_order(epoch)
            pending: deque = deque()  # [upos, base, cols, consumed, n]
            buffered = 0
            fetch = self._fetch_units(order, pos, off)
            try:
                for upos, base, cols, n in fetch:
                    self._check_template(cols)
                    pending.append([upos, base, cols, 0, n])
                    buffered += n
                    while buffered >= B:
                        batch, buffered, resume_pos, resume_off = self._emit(
                            pending, buffered, B
                        )
                        yield batch, (epoch, resume_pos, resume_off)
            finally:
                # closing the iterator mid-epoch must release the fetch
                # pipeline's in-flight accounting NOW — relying on GC to
                # close the sub-generator leaves the prefetch-depth gauge
                # stuck until an arbitrary later collection
                fetch.close()
            if buffered and ds.remainder != "drop":
                batch, _, _, _ = self._emit(pending, buffered, buffered)
                if ds.remainder == "pad" and buffered < B:
                    batch = {
                        p: _pad_rows(a, B) for p, a in batch.items()
                    }
                yield batch, (epoch + 1, 0, 0)
            epoch += 1
            pos = 0
            off = 0

    def _emit(self, pending: deque, buffered: int, take: int):
        """Assemble one `take`-row batch from the buffered spans; returns
        (batch, remaining buffered rows, cursor pos, cursor off)."""
        parts: dict[tuple, list] = {}
        need = take
        last_upos = -1
        while need:
            e = pending[0]
            upos, base, cols, consumed, n = e
            chunk = min(need, n - consumed)
            for p, a in cols.items():
                parts.setdefault(p, []).append(a[consumed : consumed + chunk])
            e[3] = consumed + chunk
            need -= chunk
            last_upos = upos
            if e[3] == n:
                pending.popleft()
        batch = {
            p: (ps[0] if len(ps) == 1 else np.concatenate(ps))
            for p, ps in parts.items()
        }
        if pending:
            head = pending[0]
            cursor = (head[0], head[1] + head[3])
        else:
            cursor = (last_upos + 1, 0)
        _metrics.inc("dataset_batches_total")
        _metrics.inc("dataset_rows_total", take)
        return batch, buffered - take, cursor[0], cursor[1]

    def _check_template(self, cols: dict) -> None:
        """Cross-file consistency: every unit must deliver the same columns
        with the same dtype/trailing shape, or concatenation would silently
        upcast (or crash deep in numpy with no file context)."""
        tmpl = {p: (a.dtype, a.shape[1:]) for p, a in cols.items()}
        if self._dtypes is None:
            self._dtypes = tmpl
            return
        if tmpl != self._dtypes:
            raise ParquetFileError(
                f"dataset: unit schema mismatch: {tmpl} != {self._dtypes} "
                "(files in one dataset must agree on columns and types)"
            )

    # -- unit fetch (the bounded prefetch pipeline) ----------------------------

    def _fetch_units(self, order: list[int], start_pos: int, start_off: int):
        """Yield (order position, base row offset, column arrays, rows) for
        every unit from start_pos on that delivers rows, in order, decoding
        up to `prefetch` units ahead on the pqt-data pool."""
        ds = self._ds
        units = ds.plan.units
        ctl = ds._controller
        depth = ds.prefetch if ctl is None else ctl.prefetch_target
        if depth <= 0:
            for k in range(start_pos, len(order)):
                off = start_off if k == start_pos else 0
                # the synchronous path waits for the WHOLE decode: record
                # it, or wait_share would read 0% exactly when the consumer
                # is 100% decode-bound (the tuning signal inverted)
                with timed_stage("dataset.wait") as w:
                    cols, n = self._load_unit(units[order[k]], off)
                _metrics.observe("dataset_wait_seconds", w.seconds)
                if cols is not None and n > 0:
                    yield k, off, cols, n
            return
        pool = ds._worker_pool()
        pending: deque = deque()
        nxt = start_pos
        ra_scheduled: set[int] = set()

        def readahead():
            # one IO stage ahead of decode: while pqt-data decodes the
            # window [start..nxt), pqt-io streams the NEXT units' planned
            # byte ranges into the shared block cache (advisory: budget
            # overflow drops, decode reads through either way)
            if ds._readahead is None:
                return
            for j in range(nxt, min(nxt + max(depth, 1), len(order))):
                if j in ra_scheduled:
                    continue
                ra_scheduled.add(j)
                unit = units[order[j]]
                ranges = ds._unit_ranges(unit)
                if ranges:
                    ds._readahead.schedule(unit.path, ranges)

        def fill():
            nonlocal nxt, depth
            if ctl is not None:
                # re-read the target each refill: the controller moves it
                # between batches, and the window tracks it immediately —
                # up (more submits now) or down (drain to the new bound)
                depth = ctl.prefetch_target
            added = 0
            while nxt < len(order) and len(pending) < depth:
                off = start_off if nxt == start_pos else 0
                pending.append(
                    (nxt, off, instrumented_submit(pool, self._load_unit,
                                                   units[order[nxt]], off,
                                                   pool="pqt-data"))
                )
                nxt += 1
                added += 1
            if added:
                _inflight_add(added)
            readahead()

        fill()
        try:
            while pending:
                k, off, fut = pending.popleft()
                try:
                    with timed_stage("dataset.wait") as w:
                        cols, n = fut.result()
                finally:
                    _inflight_add(-1)  # popped units always leave the gauge
                _metrics.observe("dataset_wait_seconds", w.seconds)
                if ctl is not None and ctl.tick():
                    ds._apply_controller_targets()
                fill()
                if cols is not None and n > 0:
                    yield k, off, cols, n
        finally:
            if pending:
                _inflight_add(-len(pending))
            for _k, _o, fut in pending:
                fut.cancel()

    def _load_unit(self, unit, row_offset: int):
        """Decode one (file, row group) into batchable column arrays,
        sliced from `row_offset`. Runs on pqt-data worker threads (the trace
        and log context arrive via instrumented_submit). Returns (None, 0)
        for a unit the on_error policy dropped."""
        ds = self._ds
        t0 = time.perf_counter()

        def _skipped(reason: str):
            # the noteworthy event (rate-limited) + the flight record: one
            # /v1/debug listing shows the skipped unit next to the serve
            # traffic that may have been racing it
            bump("dataset_units_skipped")
            log_event(
                "unit_quarantined", level="warning",
                file=unit.path, group=unit.row_group, reason=reason,
            )
            _recorder().record(
                "dataset.unit", status="skipped",
                duration_s=time.perf_counter() - t0,
                detail={"file": unit.path, "group": unit.row_group,
                        "reason": reason},
            )
            return None, 0

        with span(
            "dataset.unit", {"file": unit.path, "group": unit.row_group}
        ):
            try:
                reader = FileReader(
                    unit.path,
                    columns=ds.columns,
                    metadata=ds.plan.metas[unit.file_index],
                    schema=ds._file_schema(unit.file_index),
                    validate_crc=ds.validate_crc,
                    on_error=ds.on_error,
                    block_cache=ds._block_cache,
                    coalesce_gap="auto" if ds.io_autotune else None,
                )
            except PARQUET_ERRORS + (OSError,):
                if ds.on_error == "raise":
                    raise
                return _skipped("open_failed")
            try:
                read_cols = None
                normalized = None
                if ds.filter_rows:
                    # extend the read set to cover filter leaves; the
                    # projection (reader._selected) prunes them back out
                    # below so filter-only columns never need a batch form
                    from ..core.filter import normalize_dnf

                    normalized = normalize_dnf(reader.schema, ds.filters)
                    read_cols = reader._columns_with_filters(
                        ds.columns, normalized
                    )
                chunks = reader._read_row_group(
                    unit.row_group, read_cols, pack=False
                )
                if not chunks:
                    # quarantined by on_error (or empty selection)
                    return _skipped("quarantined")
                mask = None
                if normalized is not None:
                    # a VecFilterError here is a deterministic shape decline
                    # (it would quarantine EVERY unit) — always a raise, no
                    # on_error swallowing
                    from ..core.filter_vec import dnf_mask

                    nrows = int(
                        reader.row_group(unit.row_group).num_rows or 0
                    )
                    mask = dnf_mask(chunks, normalized, nrows)
                keep = reader._selected
                cols = {
                    p: self._batch_array(p, cd, reader.schema.column(p))
                    for p, cd in chunks.items()
                    if keep is None or p in keep
                }
            except OSError:
                # transport failure mid-decode (a retry ladder exhausted,
                # a circuit breaker fast-failing a blacked-out source):
                # under "skip"/"null" the unit quarantines exactly like a
                # corrupt one — the stream degrades in typed, counted
                # steps instead of killing the train loop
                if ds.on_error == "raise":
                    raise
                return _skipped("io_failed")
            finally:
                reader.close()
        lens = {a.shape[0] for a in cols.values()}
        if len(lens) != 1:
            raise ParquetFileError(
                f"dataset: columns disagree on row count in "
                f"{unit.path} group {unit.row_group}: {sorted(lens)}"
            )
        n = lens.pop()
        if mask is not None and not mask.all():
            # row filtering happens BEFORE the resume offset: row_offset
            # counts positions in the FILTERED stream, so a resumed
            # iterator replays byte-identically whether or not the
            # original run filtered
            bump("dataset_units_row_filtered")
            cols = {p: a[mask] for p, a in cols.items()}
            n = int(mask.sum())
            if not n:
                return None, 0
        if row_offset:
            if row_offset >= n:
                return None, 0
            cols = {p: a[row_offset:] for p, a in cols.items()}
            n -= row_offset
        _recorder().record(
            "dataset.unit",
            duration_s=time.perf_counter() - t0,
            nbytes=sum(int(a.nbytes) for a in cols.values()),
            detail={"file": unit.path, "group": unit.row_group, "rows": n},
        )
        return cols, n

    def _batch_array(self, path, cd, leaf) -> np.ndarray:
        """One decoded chunk -> a row-aligned numpy array (the host-side
        analogue of iter_device_batches' _array_of)."""
        name = ".".join(path)
        if cd.rep_levels is not None or leaf.max_rep > 0:
            raise ParquetFileError(
                f"dataset: column {name} is repeated; its leaf slots are "
                "not rows, so it cannot batch (project it out)"
            )
        values = cd.values
        if isinstance(values, ByteArrayData):
            raise ParquetFileError(
                f"dataset: column {name} is a raw byte array with no fixed-"
                "width batch form (project it out, or encode it as a "
                "fixed-size or integer feature upstream)"
            )
        arr = np.asarray(values)
        n = cd.num_values
        if arr.shape[0] != n:  # nulls: values are dense non-null cells
            if self._ds.nullable != "zero":
                raise ParquetFileError(
                    f"dataset: column {name} contains nulls; pass "
                    'nullable="zero" to zero-fill them (or filter upstream)'
                )
            valid = np.asarray(cd.def_levels) == leaf.max_def
            out = np.zeros((n,) + arr.shape[1:], dtype=arr.dtype)
            out[valid] = arr
            arr = out
        return arr


def _pad_rows(a, target: int):
    """Zero-pad the leading axis to `target` rows (remainder="pad")."""
    if a.shape[0] >= target:
        return a
    pad = np.zeros((target - a.shape[0],) + a.shape[1:], dtype=a.dtype)
    return np.concatenate([a, pad])
