"""Device-resident residual filtering: normalized DNF -> HBM boolean row mask.

The device twin of core/filter_vec.dnf_mask: the same (already normalized)
DNF evaluates over one row group's DEVICE-DELIVERED columns ({leaf path:
kernels.pipeline.DeviceColumn}) and yields a jax boolean row mask that never
leaves HBM — it feeds device partial aggregation directly, or
kernels/device_ops.mask_take_device for the compaction gather
(predicate -> mask -> gather, SURVEY §7.1). Value comparisons run as
device_ops.predicate_mask_device kernels over the chunk's dense values;
LIST `contains` lifts element hits to rows through
list_contains_mask_device; level-derived structure (validity, record
starts) is computed from the HOST-side level streams DeviceColumn carries
and uploaded once per referenced leaf.

Semantics are pinned to the host vec engine bracket-for-bracket:

  * comparisons happen in the PHYSICAL storage domain against the
    (stat_lo, stat_hi) bracket normalize_filters computed — lo == hi means
    exactly representable, lo != hi means the value falls BETWEEN stored
    values (equality impossible, ordered ops use the exact end);
  * unsigned logical types compare as bit-pattern views
    (lax.bitcast_convert_type + the sub-width mask — the device form of
    filter_vec._numeric_view);
  * dictionary-preserved chunks compare their (small, host-side)
    dictionary ONCE with the host engine's own comparators, then one
    device gather through the resident indices lifts the verdict to rows
    (dict_verdict_device: with predicate_mask_device, what the device
    trace shows under the scope pqt.query_mask);
  * both null conventions ("row" and "arrow") are implemented, matching
    filter_vec._leaf_mask including pyarrow's null-keeping not_in and the
    float32 in-list cast decline.

Anything outside that envelope — non-dictionary byte arrays (no device
value ordering), out-of-range brackets, unorderable physical domains —
raises the typed DeviceFilterError and the CALLER falls back to the host
engine (counted, never silent): exactness always wins over residency.
"""

from __future__ import annotations

import numpy as np

try:
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover - callers gate on jax availability
    jax = None
    jnp = None

from ..kernels.device_ops import (
    dict_verdict_device,
    list_contains_mask_device,
    predicate_mask_device,
    prefix_sum,
)
from .arrays import ByteArrayData
from .filter import FilterError
from .filter_vec import VecFilterError, _bytes_compare, _numeric_view, _raw_compare
from .stats import column_is_unsigned

__all__ = ["DeviceFilterError", "device_dnf_mask"]

# `in`-list sets compare as one equality kernel per member; a pathological
# member list would turn into a launch storm, so it takes the host engine
_MAX_MEMBERS = 64


class DeviceFilterError(FilterError):
    """The device mask pipeline cannot evaluate this predicate over these
    device-delivered columns (no device value form, uncovered shape,
    out-of-range bracket). Callers fall back to the host engine — vec mask
    or scalar walk — which is exact for everything; same contract as
    filter_vec.VecFilterError one rung down the ladder."""


def device_dnf_mask(group: dict, dnf, n_rows: int, *, null_mode: str = "row"):
    """bool[n_rows] DEVICE row mask of a normalized DNF over one row
    group's device-delivered columns ({leaf path: DeviceColumn}). Raises
    DeviceFilterError when any referenced predicate cannot run on device —
    all or nothing, so engines never mix within one group and outputs stay
    identical to the host walk whichever engine runs."""
    if jnp is None:
        raise DeviceFilterError("filter_device: jax is not importable")
    if null_mode not in ("row", "arrow"):
        raise ValueError('null_mode must be "row" or "arrow"')
    ctx: dict = {}
    out = None
    for conj in dnf:
        m = None
        for entry in conj:
            lm = _leaf_mask(group, entry, n_rows, null_mode, ctx)
            m = lm if m is None else (m & lm)
        if m is None:  # empty conjunction is vacuously true
            return jnp.ones(n_rows, dtype=bool)
        out = m if out is None else (out | m)
    if out is None:
        return jnp.ones(n_rows, dtype=bool)
    return out


# -- per-leaf masks -------------------------------------------------------------


def _leaf_mask(group, entry, n_rows, null_mode, ctx):
    path, leaf, op, value, vlo, vhi = entry
    dc = group.get(path)
    if dc is None:
        raise DeviceFilterError(
            f"filter_device: column {'.'.join(path)} not delivered on device"
        )
    if op == "contains":
        return _contains_mask(dc, leaf, vlo, vhi, n_rows, (path, ctx))
    if leaf.max_rep != 0:
        raise DeviceFilterError(f"filter_device: {'.'.join(path)} is repeated")
    if dc.num_values != n_rows:
        raise DeviceFilterError(
            f"filter_device: {'.'.join(path)}: {dc.num_values} level entries "
            f"for {n_rows} rows"
        )
    valid = None
    if leaf.max_def > 0 and dc.def_levels is not None:
        v = np.asarray(dc.def_levels) == leaf.max_def
        if not v.all():
            valid = v
    if op == "is_null":
        if valid is None:
            return jnp.zeros(n_rows, dtype=bool)
        return jnp.asarray(~valid)
    if op == "not_null":
        if valid is None:
            return jnp.ones(n_rows, dtype=bool)
        return jnp.asarray(valid)
    if op in ("in", "not_in") and null_mode == "arrow":
        # same decline as filter_vec._leaf_mask: pyarrow's is_in CASTS the
        # value set to float32, diverging from exact semantics — whichever
        # host engine takes the fallback decides, and results stay
        # value-identical to the to_arrow path
        from ..meta.parquet_types import Type

        if leaf.type == Type.FLOAT and isinstance(vlo, list) and any(
            lo is not None
            and isinstance(lo, float)
            and float(np.float32(lo)) != lo
            for lo, _ in vlo
        ):
            raise DeviceFilterError(
                f"filter_device: {leaf.path_str}: in-list member inexact in "
                "float32 (pyarrow is_in casts the value set)"
            )
    cmp = _dense_compare(dc, leaf, op, vlo, vhi, (path, ctx))
    nd = int(valid.sum()) if valid is not None else n_rows
    if cmp.shape[0] != nd:
        raise DeviceFilterError(
            f"filter_device: {'.'.join(path)}: {cmp.shape[0]} dense values "
            f"for {nd} defined cells"
        )
    if op == "not_in" and null_mode == "arrow":
        # pyarrow's pc.invert(pc.is_in(...)) maps null to True: nulls KEPT
        if valid is None:
            return cmp
        v, didx = _valid_expand(valid, nd, ctx, path)
        if nd == 0:
            return jnp.asarray(~valid)
        return (~v) | (v & cmp[didx])
    if valid is None:
        return cmp
    if nd == 0:
        return jnp.zeros(n_rows, dtype=bool)
    v, didx = _valid_expand(valid, nd, ctx, path)
    return v & cmp[didx]


def _valid_expand(valid_np, nd, ctx, path):
    """(device validity mask, dense-index gather map) for one leaf: entry i
    reads dense cell cumsum(valid)[i] - 1 — uploaded once per path, shared
    by every predicate of the DNF that references the column."""
    key = ("valid", path)
    hit = ctx.get(key)
    if hit is not None:
        return hit
    v = jnp.asarray(valid_np)
    didx = jnp.clip(
        prefix_sum(v.astype(jnp.int32)) - 1, 0, max(nd - 1, 0)
    )
    ctx[key] = (v, didx)
    return v, didx


def _contains_mask(dc, leaf, vlo, vhi, n_rows, ckey):
    """List-slot membership on device: the dense element equality mask
    scatters through the (host-carried, uploaded-once) level streams to row
    membership — list_contains_mask_device, the kernel twin of
    filter_vec._contains_mask."""
    if dc.rep_levels is None:
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: contains without repetition levels"
        )
    rl = np.asarray(dc.rep_levels)
    if len(rl) == 0:
        return jnp.zeros(n_rows, dtype=bool)
    if int(rl[0]) != 0:
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: stream opens mid-record"
        )
    if int((rl == 0).sum()) != n_rows:
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: record count != row count"
        )
    if dc.def_levels is not None:
        dfl = np.asarray(dc.def_levels).astype(np.int32, copy=False)
    else:
        dfl = np.full(len(rl), leaf.max_def, dtype=np.int32)
    nd = int((dfl == leaf.max_def).sum())
    dm = _dense_compare(dc, leaf, "==", vlo, vhi, ckey)
    if dm.shape[0] != nd:
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: level/value mismatch"
        )
    rows, _n = list_contains_mask_device(
        jnp.asarray(rl.astype(np.int32, copy=False)),
        jnp.asarray(dfl),
        dm,
        leaf.max_def,
    )
    return rows[:n_rows]


# -- dense value comparison -----------------------------------------------------


def _dense_compare(dc, leaf, op, vlo, vhi, ckey):
    """bool DEVICE mask over the chunk's dense (non-null) values for one
    value op, in the physical domain — predicate_mask_device for resident
    numerics, a host dictionary compare + device gather for
    dictionary-preserved chunks."""
    if vlo is None:
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: no orderable physical form"
        )
    if getattr(dc, "double_form", None) is not None:
        # a DOUBLE delivered as uint64 patterns or as float32 (doubles=) no
        # longer orders or compares as the file's float64: the host engine
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: delivered as {dc.double_form}, "
            "the float64 comparison stays on the host"
        )
    if op in ("in", "not_in"):
        if any(lo is None for lo, _ in vlo):
            raise DeviceFilterError(
                f"filter_device: {leaf.path_str}: unorderable in-list member"
            )
        if len(vlo) > _MAX_MEMBERS:
            raise DeviceFilterError(
                f"filter_device: {leaf.path_str}: in-list of {len(vlo)} "
                f"members (> {_MAX_MEMBERS}) takes the host engine"
            )
        m = _member_mask(dc, leaf, vlo, ckey)
        return ~m if op == "not_in" else m
    if dc.values is None and dc.indices is not None and dc.dictionary is not None:
        # dictionary-preserved chunk: the host engine compares the (small)
        # dictionary once, one device gather lifts it through the indices
        dcmp = _host_compare(dc.dictionary, leaf, op, vlo, vhi, ckey)
        return dict_verdict_device(jnp.asarray(dcmp), dc.indices)
    if dc.values is None:
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: no device value form "
            "(raw byte arrays have no resident ordering)"
        )
    return _device_compare(dc.values, leaf, op, vlo, vhi)


def _member_mask(dc, leaf, brackets, ckey):
    """OR of equality masks for the in-list members (an inexact bracket can
    equal no stored value: exact=False contributes all-False, matching the
    host engine's exact-members-only isin)."""
    via_dict = (
        dc.values is None and dc.indices is not None and dc.dictionary is not None
    )
    if via_dict:
        exact = [lo for lo, hi in brackets if lo == hi]
        m = _host_dict_members(dc.dictionary, leaf, exact, ckey)
        return dict_verdict_device(jnp.asarray(m), dc.indices)
    if dc.values is None:
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: no device value form "
            "(raw byte arrays have no resident ordering)"
        )
    m = None
    for lo, hi in brackets:
        em = _device_compare(dc.values, leaf, "==", lo, hi)
        m = em if m is None else (m | em)
    if m is None:
        return jnp.zeros(dc.values.shape[0], dtype=bool)
    return m


def _host_dict_members(dictionary, leaf, members, ckey):
    """np bool mask over a HOST dictionary for the exactly-representable
    in-list members — filter_vec._member_mask's target compare, reused so
    bytes/unsigned semantics stay single-sourced."""
    try:
        if not members:
            return np.zeros(len(dictionary), dtype=bool)
        if isinstance(dictionary, ByteArrayData):
            m = None
            for b in members:
                em = _bytes_compare(dictionary, "==", b, ckey)
                m = em if m is None else (m | em)
            return m
        arr = np.asarray(dictionary)
        if arr.ndim != 1:
            m = None
            for b in members:
                em = _raw_compare(dictionary, leaf, "==", b, b, ckey)
                m = em if m is None else (m | em)
            return m
        try:
            return np.isin(_numeric_view(arr, leaf), np.array(members))
        except (OverflowError, TypeError, ValueError) as e:
            raise VecFilterError(
                f"filter_device: {leaf.path_str}: in-list not comparable: {e}"
            ) from None
    except VecFilterError as e:
        raise DeviceFilterError(str(e)) from None


def _host_compare(dictionary, leaf, op, vlo, vhi, ckey):
    try:
        return _raw_compare(dictionary, leaf, op, vlo, vhi, ckey)
    except VecFilterError as e:
        raise DeviceFilterError(str(e)) from None


def _device_compare(values, leaf, op, vlo, vhi):
    """predicate_mask_device over resident values, with the bracket coerced
    to the array's dtype HOST-SIDE (a weak python scalar would re-promote
    on device; an out-of-range bracket declines instead of wrapping)."""
    if values.ndim == 2:
        return _fixed_compare(values, op, vlo)
    dt = np.dtype(values.dtype.name)
    if dt == np.bool_:
        # mirror filter_vec._raw_compare: booleans compare as int8
        if not isinstance(vlo, (bool, int, np.integer)) or not isinstance(
            vhi, (bool, int, np.integer)
        ):
            raise DeviceFilterError(
                f"filter_device: {leaf.path_str}: non-integer bracket on bool"
            )
        return predicate_mask_device(
            values.astype(jnp.int8),
            op,
            np.int8(int(vlo)),
            np.int8(int(vhi)),
            bool(int(vlo) == int(vhi)),
        )
    arr = _device_numeric_view(values, leaf)
    dt = np.dtype(arr.dtype.name)
    if dt.kind in "iu":
        if not isinstance(vlo, (int, np.integer)) or not isinstance(
            vhi, (int, np.integer)
        ):
            raise DeviceFilterError(
                f"filter_device: {leaf.path_str}: non-integer bracket on an "
                "integer column"
            )
        info = np.iinfo(dt)
        if int(vlo) < info.min or int(vhi) > info.max:
            raise DeviceFilterError(
                f"filter_device: {leaf.path_str}: bracket outside {dt} range"
            )
        lo, hi = dt.type(int(vlo)), dt.type(int(vhi))
    elif dt.kind == "f":
        try:
            lo, hi = dt.type(vlo), dt.type(vhi)
        except (OverflowError, TypeError, ValueError) as e:
            raise DeviceFilterError(
                f"filter_device: {leaf.path_str}: bracket not representable: {e}"
            ) from None
    else:
        raise DeviceFilterError(
            f"filter_device: {leaf.path_str}: uncovered device dtype {dt}"
        )
    try:
        return predicate_mask_device(arr, op, lo, hi, bool(vlo == vhi))
    except ValueError as e:
        raise DeviceFilterError(f"filter_device: {leaf.path_str}: {e}") from None


def _device_numeric_view(arr, leaf):
    """The resident array in its COMPARISON domain — the device form of
    filter_vec._numeric_view: unsigned logical types reinterpret the stored
    bit pattern (bitcast + sub-width mask)."""
    if not column_is_unsigned(leaf):
        return arr
    from .assembly import logical_kind

    kind = logical_kind(leaf)
    bits = kind[1] if isinstance(kind, tuple) and kind[0] == "uint" else None
    if arr.dtype == jnp.int32:
        arr = jax.lax.bitcast_convert_type(arr, jnp.uint32)
    elif arr.dtype == jnp.int64:
        arr = jax.lax.bitcast_convert_type(arr, jnp.uint64)
    if bits is not None and bits < np.dtype(arr.dtype.name).itemsize * 8:
        arr = arr & np.dtype(arr.dtype.name).type((1 << bits) - 1)
    return arr


def _fixed_compare(arr, op, value):
    """FIXED_LEN_BYTE_ARRAY rows ((n, width) uint8) on device: equality
    family only, exactly like filter_vec._fixed_compare."""
    if op not in ("==", "!="):
        raise DeviceFilterError(
            "filter_device: ordered comparison on fixed-width bytes"
        )
    b = bytes(value)
    if arr.shape[1] != len(b):
        eq = jnp.zeros(arr.shape[0], dtype=bool)
    elif arr.shape[1] == 0:
        eq = jnp.ones(arr.shape[0], dtype=bool)
    else:
        eq = jnp.all(arr == jnp.asarray(np.frombuffer(b, dtype=np.uint8)), axis=1)
    return eq if op == "==" else ~eq
