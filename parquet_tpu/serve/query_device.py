"""Device-resident query units: filter + partially aggregate in HBM.

POST /v1/query with an attached device backend (ServeConfig(device=...))
routes each unit (one row group of one file) through the reader's device
delivery instead of to_arrow: columns decode straight into device memory,
the residual predicate evaluates as a resident boolean mask
(core/filter_device — host vec engine fallback, typed and counted), and
each aggregate reduces to ONE masked jnp reduction
(kernels/device_ops.masked_agg_device) whose scalar result is the only
byte that crosses back to the host. The partial feeds the exact
pyarrow-pinned merge in serve/aggregate.py unchanged — device and host
units mix freely within one request because both produce the same
((groups, types), scanned, matched) shape with the same value semantics.

The ENGAGEMENT ENVELOPE is deliberately narrow and typed: global (no
group_by) count/sum/min/max over flat integer leaves (signed and unsigned,
compared and summed in their bit-pattern view domain), count over anything
flat. Everything else — group_by (pyarrow's hash-groupby semantics),
float sum (reduction order), decimal/temporal logicals (arrow type
domains) — raises DeviceQueryError and the executor reruns the unit on
the host vec engine, counted per query_device_units_total{engine=...}.
Exactness always wins over residency: int sums wrap in two's complement
exactly like pyarrow's unchecked int64/uint64 kernels, min/max of zero
matching rows is null, count skips nulls — the differential suite pins
device == host byte-for-byte.
"""

from __future__ import annotations

import numpy as np

from ..core.filter_vec import VecFilterError
from ..meta.parquet_types import Type

__all__ = ["DeviceQueryError", "device_unit_partial"]


class DeviceQueryError(Exception):
    """This unit's query shape cannot run device-resident (group_by,
    non-integer aggregate domain, undeliverable column, filter the whole
    engine ladder declined). The executor falls back to the host path —
    same answer, counted."""


def _require(cond: bool, why: str) -> None:
    if not cond:
        raise DeviceQueryError(f"query_device: {why}")


def _agg_leaf(schema, name: str):
    try:
        leaf = schema.column(tuple(name.split(".")))
    except Exception as e:
        raise DeviceQueryError(f"query_device: column {name!r}: {e}") from None
    _require(leaf.is_leaf, f"column {name!r} is not a leaf")
    _require(leaf.max_rep == 0, f"column {name!r} is repeated")
    return leaf


def _int_domain(leaf):
    """(unsigned,) engagement check for sum/min/max: plain signed or
    unsigned integers only — every other logical domain (decimal, temporal,
    float NaN skipping, int96) keeps pyarrow's kernels authoritative."""
    from ..core.assembly import logical_kind
    from ..core.stats import column_is_unsigned

    _require(
        leaf.type in (Type.INT32, Type.INT64),
        f"column {leaf.path_str}: non-integer physical type",
    )
    unsigned = column_is_unsigned(leaf)
    if not unsigned:
        _require(
            logical_kind(leaf) is None,
            f"column {leaf.path_str}: logical domain needs pyarrow semantics",
        )
    return unsigned


def _dense_values(dc, leaf):
    """The chunk's dense values as a resident jax array (dictionary-encoded
    numeric chunks expand with one small upload + gather)."""
    import jax.numpy as jnp

    if dc.values is not None:
        _require(
            getattr(dc.values, "ndim", 1) == 1,
            f"column {leaf.path_str}: no 1-D device value form",
        )
        return dc.values
    if dc.indices is not None and dc.dictionary is not None:
        d = dc.dictionary
        if isinstance(d, np.ndarray) and d.ndim == 1:
            return jnp.asarray(d)[dc.indices]
    raise DeviceQueryError(
        f"query_device: column {leaf.path_str}: no device value form"
    )


def _validity(dc, leaf):
    """Host bool[num_rows] validity (None = all valid)."""
    if leaf.max_def > 0 and dc.def_levels is not None:
        v = np.asarray(dc.def_levels) == leaf.max_def
        if not v.all():
            return v
    return None


def device_unit_partial(reader, row_group: int, query, filters, device=None):
    """One unit's ((groups, types), scanned, matched) partial, computed
    device-resident. Raises DeviceQueryError when the query shape is
    outside the device envelope — the caller falls back to the host path
    (and counts it)."""
    try:
        import jax.numpy as jnp

        from ..core.filter_device import _device_numeric_view
        from ..kernels.device_ops import masked_agg_device
        from ..kernels.pipeline import DeviceDoubleError
    except ImportError as e:  # pragma: no cover - jax-less deployment
        raise DeviceQueryError(f"query_device: jax unavailable: {e}") from None

    _require(not query.group_by, "group_by needs pyarrow's hash groupby")
    schema = reader.schema
    aggs = query.aggregates
    plans = []  # (op, leaf|None, unsigned)
    paths = []
    for a in aggs:
        if a.column is None:
            plans.append(("count*", None, False))
            continue
        leaf = _agg_leaf(schema, a.column)
        _require(
            a.op in ("count", "sum", "min", "max"), f"unsupported op {a.op!r}"
        )
        unsigned = False
        if a.op != "count":
            unsigned = _int_domain(leaf)
        plans.append((a.op, leaf, unsigned))
        if leaf.path not in paths:
            paths.append(leaf.path)

    normalized = None
    if filters is not None:
        from ..core.filter import normalize_dnf

        normalized = normalize_dnf(schema, filters)
        for conj in normalized:
            for e in conj:
                if e[0] not in paths:
                    paths.append(e[0])

    n = int(reader.row_group(row_group).num_rows or 0)
    try:
        group = reader.read_row_group_device(
            row_group, paths or None, device=device
        )
    except DeviceDoubleError as e:
        # a DOUBLE filter/count column on a device without native f64:
        # the unit is exact on the host (host_fallback). The forms a TPU
        # holds exactly (read_row_group_device's doubles="bits" /
        # "float32") neither order nor sum as the file's float64, so this
        # lane does not ask for them
        raise DeviceQueryError(f"query_device: {e}") from None

    mask = None
    matched = n
    if normalized is not None:
        # the to_arrow host path filters with pyarrow null conventions, so
        # the resident mask uses the SAME "arrow" mode; the engine ladder
        # inside _device_group_mask counts its own declines, and a shape
        # even the host vec engine refuses declines the whole unit
        try:
            with reader._devctx(device):
                mask = reader._device_group_mask(
                    row_group, group, normalized, n, null_mode="arrow"
                )
                matched = int(jnp.sum(mask))
        except VecFilterError as e:
            raise DeviceQueryError(f"query_device: {e}") from None

    vals: list = []
    types: list = [None] * len(aggs)
    import pyarrow as pa

    from ..utils.trace import span

    with reader._devctx(device), span(
        "query.aggregate", {"group": row_group, "aggs": len(aggs)}
    ):
        for j, (op, leaf, unsigned) in enumerate(plans):
            if op == "count*":
                vals.append(matched)
                continue
            dc = group.get(leaf.path)
            _require(dc is not None, f"column {leaf.path_str} not delivered")
            valid = _validity(dc, leaf)
            if op == "count":
                # count skips nulls: |mask & valid| with no value math at all
                if valid is None:
                    cnt = (
                        matched
                        if mask is not None
                        else int(dc.num_values)
                    )
                elif mask is None:
                    cnt = int(valid.sum())
                else:
                    cnt = int(jnp.sum(mask & jnp.asarray(valid)))
                vals.append(cnt)
                continue
            dense = _dense_values(dc, leaf)
            nd = int(valid.sum()) if valid is not None else n
            _require(
                dense.shape[0] == nd,
                f"column {leaf.path_str}: dense length mismatch",
            )
            # the aggregate runs in the column's COMPARISON domain (unsigned
            # bit-pattern views), widened to the 64-bit merge domain pyarrow
            # uses (sum promotes; min/max values embed exactly)
            view = _device_numeric_view(dense, leaf)
            c64 = view.astype(jnp.uint64 if unsigned else jnp.int64)
            if mask is None:
                dm = jnp.ones(nd, dtype=bool)
                live = nd
            elif valid is None:
                dm = mask
                live = matched
            else:
                dm = mask[jnp.asarray(np.flatnonzero(valid))]
                live = None
            if live is None:
                live = int(masked_agg_device(c64, dm, "count"))
            if live == 0:
                # pyarrow sum/min/max over zero (non-null, matching) values
                # is null
                vals.append(None)
                continue
            r = masked_agg_device(c64, dm, op)
            vals.append(int(r))
            types[j] = pa.uint64() if unsigned else pa.int64()
    return ({(): vals}, types), n, matched
