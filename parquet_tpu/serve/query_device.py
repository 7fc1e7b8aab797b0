"""Device-resident query units: filter, group and partially aggregate in HBM.

POST /v1/query with an attached device backend (ServeConfig(device=...))
routes each unit (one row group of one file) through the reader's device
delivery instead of to_arrow: columns decode straight into device memory,
the residual predicate evaluates as a resident boolean mask
(core/filter_device — host vec engine fallback, typed and counted), and the
aggregates reduce under it — a global unit one masked reduction an aggregate
(kernels/device_ops masked_agg_device, or expr_agg_device for an expression),
a grouped unit ALL of its groups and aggregates in one program
(group_agg_device) — whose scalars are the only bytes that cross back to the
host, all of a unit's in ONE fetch. The partial feeds the exact
pyarrow-pinned merge in serve/aggregate.py unchanged — device and host units
mix freely within one request because both produce the same ((groups, types),
scanned, matched) shape with the same value semantics.

The ENGAGEMENT ENVELOPE is deliberately narrow and typed. Aggregates, over
flat leaves:

  * count over anything flat;
  * sum/min/max over plain integers, signed and unsigned, compared and
    summed in their bit-pattern view domain (a sum wraps in two's
    complement exactly like pyarrow's unchecked int64/uint64 kernels);
  * min/max over integer-backed DECIMAL (INT32 / INT64) and DATE leaves, in
    their unscaled integer domain, the partial typed as the leaf
    (decimal128(p, s), date32);
  * sum over an integer-backed DECIMAL, and sum/min/max over an arithmetic
    expression (serve/expr.py: * + - over signed integer and integer-backed
    DECIMAL columns and literals, no nulls in the chunk), as ONE fused
    program in wrapping int64 (expr_agg_device). Arrow computes these in
    128 bits, so the unit engages only where int64 is PROVED enough: from
    the chunks' own min/max statistics every node of the tree is bounded by
    interval arithmetic (|a*b| <= max|a| * max|b|, decimal scales aligned
    the way Arrow aligns them), a node of Arrow integer type has to fit
    that type and every other node int64, and for a sum the root's bound
    times the unit's rows has to stay under 2^63. A product past 38 digits
    is typed by expr.py's cap (decimal128(38, s1 + s2)) and its narrowed
    operand has to be proved inside the precision the cap leaves it, so the
    unit answers exactly where the host's checked cast would pass. A chunk
    without statistics, or a bound that does not fit, declines the unit:
    typed and counted (query_expr_overflow_declined), answered by the host.
    The partial's Arrow type is what pyarrow.compute gives the same tree
    over empty arrays of the leaves' types (decimal128(15,2) *
    decimal128(15,2) summed: decimal128(38,4)), so the merge runs in
    Arrow's own domain;
  * avg over any input sum takes: the partial is the exact pair (the sum, in
    sum's own domain and under sum's proof, and the count of the values
    summed); serve/aggregate.py merges pairs and divides once.

group_by engages where every key chunk is WHOLLY dictionary-coded and
delivered as resident indices + its host dictionary (a BYTE_ARRAY leaf: a
string or binary key), holds no null, and the key dictionaries' sizes
multiply to at most device_ops.GROUP_SLOTS (64) slots; every reduction input
is a signed domain without nulls. The group id is the mixed-radix combination
of the key indices with the dictionary sizes as runtime scalars; a slot is
mapped back to key VALUES through each chunk's own dictionary (dictionary
order is first appearance: it differs from row group to row group), a slot no
row fell in is no group (pyarrow's group_by returns the groups present), and
sum(x) and avg(x) share one reduction. Each decline is typed and counted —
query_group_declined, and query_group_decline_reasons_total{reason=}:
key_not_dictionary (a PLAIN or mixed key chunk, a numeric key, which the
reader delivers as gathered values), key_nulls, too_many_groups, key_shape (a
nested key, bytes that are not the leaf's UTF-8, a dictionary with
duplicates), input_shape (nulls or an unsigned domain in a reduction input) —
and answered by the host with the same bytes.

Everything else — float sum (reduction order), FIXED_LEN_BYTE_ARRAY
decimals, timestamps, keys of high cardinality — raises DeviceQueryError and
the executor reruns the unit on the host vec engine, counted per
query_device_units_total{engine=...}. Exactness always wins over residency:
min/max of zero matching rows is null, count skips nulls — the differential
suite pins device == host byte-for-byte.

Under a trace a unit shows four stages inside serve.aggregate: query.decode
(the device read), query.mask, query.aggregate (the launches; a grouped
unit's query.group_keys — dictionaries to key values, the slot -> key table —
nested in it) and query.sync (the one wait for the unit's scalars).
"""

from __future__ import annotations

import datetime as dt
import decimal
import functools
from typing import NamedTuple

import numpy as np

from ..core.filter_vec import VecFilterError
from ..meta.parquet_types import Type
from . import expr as _expr

_EPOCH_DATE = dt.date(1970, 1, 1)

__all__ = ["DeviceQueryError", "device_unit_partial"]


class DeviceQueryError(Exception):
    """This unit's query shape cannot run device-resident (a group_by outside
    the grouped envelope, a non-integer aggregate domain, an undeliverable
    column, a filter the whole engine ladder declined). The executor falls back to the host path —
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


def _leaf_domain(leaf):
    """(unsigned, logical Arrow type | None) of a sum/min/max input: plain
    signed or unsigned integers, and the two logical domains that are
    integers underneath — DECIMAL (unscaled) and DATE (days). Every other
    logical domain (timestamps, times, float NaN skipping, int96) keeps
    pyarrow's kernels authoritative."""
    import pyarrow as pa

    from ..core.arrow_nested import _leaf_arrow_type
    from ..core.assembly import logical_kind
    from ..core.stats import column_is_unsigned

    _require(
        leaf.type in (Type.INT32, Type.INT64),
        f"column {leaf.path_str}: non-integer physical type",
    )
    if column_is_unsigned(leaf):
        return True, None
    kind = logical_kind(leaf)
    if kind is None:
        return False, None
    typ = _leaf_arrow_type(pa, leaf)
    _require(
        kind in ("decimal", "date")
        and (pa.types.is_decimal128(typ) or pa.types.is_date32(typ)),
        f"column {leaf.path_str}: logical domain needs pyarrow semantics",
    )
    return False, typ


def _from_domain(r: int, typ):
    """A reduced integer back in its Arrow type's Python form."""
    import pyarrow as pa

    if typ is None or pa.types.is_integer(typ):
        return int(r)
    if pa.types.is_decimal(typ):
        return decimal.Decimal(int(r)).scaleb(-typ.scale)
    return _EPOCH_DATE + dt.timedelta(days=int(r))


@functools.lru_cache(maxsize=256)
def _reduced_type(op: str, typ):
    """The Arrow type pyarrow's sum/min/max gives an input of type `typ`;
    where pyarrow has no such kernel (the sum of a DATE) the unit is the
    host's, which renders pyarrow's refusal as the request's 400. Asked of
    pyarrow once a (op, type), not once a unit: an Arrow call hands the GIL
    away, and every unit of every query would wait for it again."""
    import pyarrow as pa
    import pyarrow.compute as pc

    try:
        return getattr(pc, op)(pa.array([], typ)).type
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
        raise DeviceQueryError(f"query_device: {e}") from None


def _dense_values(dc, leaf):
    """The chunk's dense values as a resident jax array (dictionary-encoded
    numeric chunks expand with one small upload + dict_gather_device's
    lookup: dense or XLA's gather by the table's length and dtype)."""
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
            from ..kernels.device_ops import dict_gather_device

            return dict_gather_device(jnp.asarray(d), dc.indices)
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


# -- expressions: bound, bind, prove ---------------------------------------------


class _OverflowDecline(DeviceQueryError):
    """The statistics cannot prove the expression inside int64 (or hold
    nothing to prove it from): the unit is the host's, counted apart."""


def _chunk_bounds(rg, leaf):
    """(min, max) of one chunk's values from its own statistics, in the
    leaf's physical integers."""
    from ..core.filter import _decode_stat, chunks_by_path

    cc = chunks_by_path(rg).get(leaf.path)
    st = None if cc is None else cc.meta_data.statistics
    if st is None or st.min_value is None or st.max_value is None:
        raise _OverflowDecline(
            f"query_device: column {leaf.path_str}: no min/max statistics to "
            "bound the expression with"
        )
    _require(
        leaf.max_def == 0 or st.null_count == 0,
        f"column {leaf.path_str}: nulls under an expression",
    )
    lo = _decode_stat(leaf, st.min_value, legacy=False)
    hi = _decode_stat(leaf, st.max_value, legacy=False)
    if not isinstance(lo, int) or not isinstance(hi, int) or lo > hi:
        raise _OverflowDecline(
            f"query_device: column {leaf.path_str}: unusable statistics"
        )
    return lo, hi


def _bind(tree, column):
    """(program, Arrow type, lo, hi) of an expression tree: the tree in the
    columns' unscaled integer domain as expr_agg_device takes it, the type
    pyarrow.compute gives the node (from the same operator over empty arrays
    of its operands' types), and the interval its values lie in. `column`
    gives (index, Arrow type, lo, hi) per column name. Raises
    _OverflowDecline where a node cannot be proved inside its domain."""
    import pyarrow as pa

    if tree[0] == "col":
        index, typ, lo, hi = column(tree[1])
        return ("col", index), typ, lo, hi
    if tree[0] == "lit":
        v = _expr.literal(tree[1])
        typ = pa.scalar(v).type
        u = v if isinstance(v, int) else int(v.scaleb(typ.scale))
        return _fits(("lit", u), typ, u, u)
    op = tree[0]
    left, lt, llo, lhi = _bind(tree[1], column)
    right, rt, rlo, rhi = _bind(tree[2], column)
    typ = _node_type(op, lt, rt)
    if op == "*":
        cap = _expr.capped_product(lt, rt)
        if cap is not None:
            # expr.py's one rule: the host casts this operand, checked, to
            # the narrower precision and raises where a value does not fit;
            # the unit runs here only where the statistics prove every value
            # fits, so both lanes answer or neither does
            lo, hi = (llo, lhi) if cap[0] == 0 else (rlo, rhi)
            limit = 10 ** cap[1].precision
            if lo <= -limit or hi >= limit:
                raise _OverflowDecline(
                    f"query_device: values in [{lo}, {hi}] are not proved "
                    f"inside {cap[1]}, the precision a 38-digit product leaves them"
                )
        ends = (llo * rlo, llo * rhi, lhi * rlo, lhi * rhi)
        return _fits((op, left, right), typ, min(ends), max(ends))
    if pa.types.is_decimal(typ):
        # Arrow adds decimals at the larger scale: the other side steps up
        left, llo, lhi = _rescaled(left, llo, lhi, typ.scale - _scale(lt))
        right, rlo, rhi = _rescaled(right, rlo, rhi, typ.scale - _scale(rt))
    if op == "+":
        return _fits((op, left, right), typ, llo + rlo, lhi + rhi)
    return _fits((op, left, right), typ, llo - rhi, lhi - rlo)


@functools.lru_cache(maxsize=256)
def _node_type(op: str, lt, rt):
    """The Arrow type pyarrow.compute gives `l op r` (expr.py's rules, the
    38-digit cap among them), from the operator over empty arrays; once a
    (op, types), as _reduced_type."""
    import pyarrow as pa

    empty = {"l": pa.array([], lt), "r": pa.array([], rt)}
    try:
        return _expr.evaluate((op, ("col", "l"), ("col", "r")), empty.__getitem__).type
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
        # the host lane raises the same, as the request's typed 400
        raise DeviceQueryError(f"query_device: {e}") from None


def _scale(typ) -> int:
    return getattr(typ, "scale", 0)


def _rescaled(program, lo, hi, digits: int):
    if digits == 0:
        return program, lo, hi
    by = 10 ** digits
    return ("*", program, ("lit", by)), lo * by, hi * by


def _fits(program, typ, lo, hi):
    """A node of Arrow integer type wraps at that type's width and the
    kernel computes in int64: both are exact only inside the narrower."""
    import pyarrow as pa

    bits = typ.bit_width if pa.types.is_integer(typ) else 64
    limit = 1 << (min(bits, 64) - 1)
    if lo < -limit or hi >= limit:
        raise _OverflowDecline(
            f"query_device: expression values in [{lo}, {hi}] are not proved "
            f"inside {bits} bits"
        )
    return program, typ, lo, hi


def _bind_expression(schema, rg, op, tree, rows) -> "_Plan":
    """One expression aggregate, ready for expr_agg_device. The proof is made
    here, from the row group's metadata alone."""
    import pyarrow as pa

    from ..core.arrow_nested import _leaf_arrow_type

    leaves: list = []

    def column(name):
        leaf = _agg_leaf(schema, name)
        unsigned, _ = _leaf_domain(leaf)
        _require(not unsigned, f"column {name!r}: unsigned under an expression")
        typ = _leaf_arrow_type(pa, leaf)
        if leaf not in leaves:
            leaves.append(leaf)
        return (leaves.index(leaf), typ, *_chunk_bounds(rg, leaf))

    program, typ, lo, hi = _bind(tree, column)
    reduced = _reduced_type(op, typ)
    if op == "sum" and max(-lo, hi) * max(rows, 1) >= 1 << 63:
        raise _OverflowDecline(
            f"query_device: a sum of {rows} values in [{lo}, {hi}] is not "
            "proved inside int64"
        )
    return _Plan("expr", op, tuple(leaves), typ=typ, program=program, reduced=reduced)


class _Plan(NamedTuple):
    """How one aggregate of a unit is computed."""

    # "count*" | "count" | "leaf" (masked_agg_device) | "expr" (expr_agg_device);
    # a grouped unit reduces "leaf" and "expr" alike in group_agg_device
    kind: str
    op: str = "count"
    leaves: tuple = ()  # the input leaf; an expression's leaves, in column order
    unsigned: bool = False
    typ: object = None  # the input's logical Arrow type (None: a plain integer)
    program: tuple | None = None  # the expression in integers, as expr_agg_device takes it
    reduced: object = None  # the aggregate's Arrow type, where the plan knows it


def _plan(schema, rg, a, rows: int) -> _Plan:
    if a.column is None:
        return _Plan("count*")
    _require(a.op in ("count", "sum", "min", "max", "avg"), f"unsupported op {a.op!r}")
    # avg's partial is the pair (sum, count of the values summed): its plan
    # is the sum's, and the pair is made where the scalars come back
    op = "sum" if a.op == "avg" else a.op
    if a.expr is not None:
        _require(op != "count", "count over an expression")
        return _bind_expression(schema, rg, op, a.expr, rows)
    leaf = _agg_leaf(schema, a.column)
    if op == "count":
        return _Plan("count", leaves=(leaf,))
    unsigned, typ = _leaf_domain(leaf)
    if op == "sum" and typ is not None:
        # Arrow sums a decimal in 128 bits: the fused kernel and its proof,
        # over the one-column tree
        return _bind_expression(schema, rg, "sum", ("col", a.column), rows)
    reduced = _reduced_type(op, typ) if typ is not None else _int64_type(unsigned)
    return _Plan("leaf", op, (leaf,), unsigned, typ, reduced=reduced)


def _int64_type(unsigned: bool):
    import pyarrow as pa

    return pa.uint64() if unsigned else pa.int64()


def device_unit_partial(reader, row_group: int, query, filters, device=None):
    """One unit's ((groups, types), scanned, matched) partial, computed
    device-resident. Raises DeviceQueryError when the query shape is
    outside the device envelope — the caller falls back to the host path
    (and counts it)."""
    try:
        import jax
        import jax.numpy as jnp

        from ..core.filter_device import _device_numeric_view
        from ..kernels.device_ops import expr_agg_device, masked_agg_device
        from ..kernels.pipeline import DeviceDoubleError
    except ImportError as e:  # pragma: no cover - jax-less deployment
        raise DeviceQueryError(f"query_device: jax unavailable: {e}") from None
    from ..utils import metrics as _metrics
    from ..utils.trace import stage

    schema = reader.schema
    rg = reader.row_group(row_group)
    n = int(rg.num_rows or 0)
    try:
        # an expression the statistics cannot bound declines here, before a
        # byte of the unit is read
        plans = [_plan(schema, rg, a, n) for a in query.aggregates]
    except _OverflowDecline:
        _metrics.inc("query_expr_overflow_declined")
        raise
    key_leaves = [_key_leaf(schema, name) for name in query.group_by]
    paths: list = [leaf.path for leaf in key_leaves]
    for plan in plans:
        for leaf in plan.leaves:
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

    try:
        with stage("query.decode", args={"group": row_group}):
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

    # every number the unit needs, device scalars and host counts alike, by
    # its slot in one list: fetched together at the end, one wait a unit
    wanted: list = []

    def later(value) -> int:
        wanted.append(value)
        return len(wanted) - 1

    def count(m):
        return masked_agg_device(m, m, "count")

    def delivered(leaf):
        dc = group.get(leaf.path)
        _require(dc is not None, f"column {leaf.path_str} not delivered")
        return dc, _validity(dc, leaf)

    def dense_of(dc, leaf, expected: int):
        dense = _dense_values(dc, leaf)
        _require(
            dense.shape[0] == expected,
            f"column {leaf.path_str}: dense length mismatch",
        )
        return dense

    mask = None
    matched = later(n)
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
                matched = later(count(mask))
        except VecFilterError as e:
            raise DeviceQueryError(f"query_device: {e}") from None

    if key_leaves:
        with reader._devctx(device), stage(
            "query.aggregate", args={"group": row_group, "aggs": len(plans)}
        ):
            slots, finish = _launch_grouped(key_leaves, plans, query, group, mask, n)
        with stage("query.sync", args={"group": row_group, "scalars": len(wanted) + 1}):
            got = jax.device_get([wanted, slots])
        return finish(got[1]), n, int(got[0][matched])

    # per aggregate: the slot of a count, or (slot of the reduced scalar,
    # slot of the count of values it reduced)
    outs: list = []
    mixed = False
    with reader._devctx(device), stage(
        "query.aggregate", args={"group": row_group, "aggs": len(plans)}
    ):
        for plan in plans:
            if plan.kind == "count*":
                outs.append(matched)
                continue
            if plan.kind == "expr":
                columns = []
                for leaf in plan.leaves:
                    dc, valid = delivered(leaf)
                    _require(
                        valid is None,
                        f"column {leaf.path_str}: nulls under an expression",
                    )
                    columns.append(dense_of(dc, leaf, n))
                    mixed |= dc.mixed
                dm = jnp.ones(n, dtype=bool) if mask is None else mask
                reduced = expr_agg_device(tuple(columns), dm, plan.program, plan.op)
                outs.append((later(reduced), matched))
                _metrics.inc("query_expr_rows", n)
                continue
            (leaf,) = plan.leaves
            dc, valid = delivered(leaf)
            if plan.kind == "count":
                # count skips nulls: |mask & valid| with no value math at all
                if valid is None:
                    outs.append(matched if mask is not None else later(int(dc.num_values)))
                elif mask is None:
                    outs.append(later(int(valid.sum())))
                else:
                    outs.append(later(count(mask & jnp.asarray(valid))))
                continue
            nd = n if valid is None else int(valid.sum())
            dense = dense_of(dc, leaf, nd)
            mixed |= dc.mixed
            # the aggregate runs in the column's COMPARISON domain (unsigned
            # bit-pattern views), widened to the 64-bit merge domain pyarrow
            # uses (sum promotes; min/max values embed exactly)
            view = _device_numeric_view(dense, leaf)
            c64 = view.astype(jnp.uint64 if plan.unsigned else jnp.int64)
            if mask is None:
                dm, live = jnp.ones(nd, dtype=bool), later(nd)
            elif valid is None:
                dm, live = mask, matched
            else:
                dm = mask[jnp.asarray(np.flatnonzero(valid))]
                live = later(count(dm))
            outs.append((later(masked_agg_device(c64, dm, plan.op)), live))
    _metrics.inc("query_expr_units", int(any(p.kind == "expr" for p in plans)))
    _metrics.inc("query_mixed_chunks", int(mixed))

    with stage("query.sync", args={"group": row_group, "scalars": len(wanted)}):
        got = jax.device_get(wanted)

    vals: list = []
    types: list = [None] * len(plans)
    for j, (a, plan, out) in enumerate(zip(query.aggregates, plans, outs)):
        if isinstance(out, int):
            vals.append(int(got[out]))
        elif int(got[out[1]]) == 0:
            # pyarrow sum/min/max over zero (non-null, matching) values is null
            vals.append(None)
        else:
            v = _from_domain(got[out[0]], plan.typ)
            vals.append((v, int(got[out[1]])) if a.op == "avg" else v)
            types[j] = plan.reduced
    return ({(): vals}, types), n, int(got[matched])


# -- grouped units ---------------------------------------------------------------


class _GroupDecline(DeviceQueryError):
    """A grouped unit outside the device lane's envelope: the host's, counted
    as query_group_declined with its reason."""

    def __init__(self, reason: str, why: str):
        super().__init__(f"query_device: group_by: {why}")
        from ..utils import metrics as _metrics

        _metrics.inc("query_group_declined")
        _metrics.inc("query_group_decline_reasons_total", reason=reason)


def _key_leaf(schema, name: str):
    try:
        return _agg_leaf(schema, name)
    except DeviceQueryError as e:
        raise _GroupDecline("key_shape", str(e)) from None


def _key_values(leaf, dictionary) -> list:
    """A key chunk's dictionary as the Python values pyarrow's group_by gives
    the same column (str for a string leaf, bytes for a binary one)."""
    import pyarrow as pa

    from ..core.arrow_nested import _leaf_arrow_type

    try:
        raw = pa.array(dictionary.to_list(), type=pa.binary())
        return raw.cast(_leaf_arrow_type(pa, leaf)).to_pylist()
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
        raise _GroupDecline("key_shape", f"key {leaf.path_str}: {e}") from None


def _remapped(program, to):
    """An expression program with its column numbers taken through `to`."""
    if program[0] == "col":
        return ("col", to[program[1]])
    if program[0] == "lit":
        return program
    return (program[0], _remapped(program[1], to), _remapped(program[2], to))


def _launch_grouped(key_leaves, plans, query, group, mask, n: int):
    """Launch one grouped unit's kernel: (the slots' device values, a
    function that builds the unit's (groups, types) from them once fetched).
    Every decline is raised here, before the launch.

    Group ids come from the key chunks' RESIDENT dictionary indices; each
    chunk's own host dictionary (whose order is first appearance, so it
    differs from row group to row group) maps a slot back to key values."""
    import jax.numpy as jnp

    from ..core.arrays import ByteArrayData
    from ..kernels.device_ops import GROUP_SLOTS, group_agg_device
    from ..utils import metrics as _metrics
    from ..utils.trace import stage

    keys, sizes = [], []
    for leaf in key_leaves:
        dc = group.get(leaf.path)
        if dc is None or dc.indices is None or not isinstance(dc.dictionary, ByteArrayData):
            # a PLAIN or mixed chunk (its writer's dictionary overflowed), or
            # a numeric key, which the reader delivers as gathered values
            raise _GroupDecline(
                "key_not_dictionary",
                f"key {leaf.path_str} is not delivered as dictionary indices",
            )
        if _validity(dc, leaf) is not None or dc.indices.shape[0] != n:
            raise _GroupDecline("key_nulls", f"key {leaf.path_str} holds nulls")
        keys.append(dc)
        sizes.append(len(dc.dictionary))
    slots = 1
    for size in sizes:
        slots *= size
    if not 0 < slots <= GROUP_SLOTS:
        raise _GroupDecline(
            "too_many_groups",
            f"the key dictionaries span {slots} slots, the kernel's bucket is {GROUP_SLOTS}",
        )

    # the distinct reduction inputs, over one shared list of columns
    columns: list = []
    leaves: list = []
    mixed = False
    for plan in plans:
        for leaf in plan.leaves:
            dc = group.get(leaf.path)
            if dc is None or _validity(dc, leaf) is not None or plan.unsigned:
                raise _GroupDecline(
                    "input_shape",
                    f"column {leaf.path_str}: nulls or an unsigned domain under group_by",
                )
            if plan.kind == "count" or leaf in leaves:
                continue  # with no nulls, count(x) is the slot's matched count
            try:
                dense = _dense_values(dc, leaf)
            except DeviceQueryError as e:
                raise _GroupDecline("input_shape", str(e)) from None
            if dense.shape[0] != n:
                raise _GroupDecline("input_shape", f"column {leaf.path_str}: dense length mismatch")
            leaves.append(leaf)
            columns.append(dense)
            mixed |= dc.mixed
    programs: dict = {}  # program -> the ops asked of it, in order of first use
    wants: list = []  # per aggregate: None (the slot's count) | (program, op)
    for plan in plans:
        if plan.kind in ("count*", "count"):
            wants.append(None)
            continue
        to = [leaves.index(leaf) for leaf in plan.leaves]
        program = _remapped(plan.program, to) if plan.kind == "expr" else ("col", to[0])
        ops = programs.setdefault(program, [])
        if plan.op not in ops:
            ops.append(plan.op)
        wants.append((program, plan.op))

    with stage("query.group_keys", args={"keys": len(keys), "slots": slots}):
        # slot -> key values: the mixed-radix digits of the slot, each through
        # its own chunk's dictionary
        values = [_key_values(leaf, dc.dictionary) for leaf, dc in zip(key_leaves, keys)]
        for leaf, vs in zip(key_leaves, values):
            if len(set(vs)) != len(vs):
                raise _GroupDecline("key_shape", f"key {leaf.path_str}: a dictionary with duplicates")
        radix = [1] * len(sizes)
        for k in range(len(sizes) - 2, -1, -1):
            radix[k] = radix[k + 1] * sizes[k + 1]
        slot_keys = [
            tuple(vs[(s // r) % size] for vs, r, size in zip(values, radix, sizes))
            for s in range(slots)
        ]

    static = tuple((program, tuple(ops)) for program, ops in programs.items())
    dm = jnp.ones(n, dtype=bool) if mask is None else mask
    fetch = group_agg_device(
        tuple(dc.indices for dc in keys),
        np.asarray([slots, *radix], dtype=np.int32),
        tuple(columns),
        dm,
        static,
    )
    _metrics.inc("query_group_units")
    _metrics.inc("query_group_rows", n)
    _metrics.inc("query_expr_units", int(any(p.kind == "expr" for p in plans)))
    _metrics.inc("query_mixed_chunks", int(mixed))
    where = {(program, op): (i, k) for i, (program, ops) in enumerate(static) for k, op in enumerate(ops)}

    def finish(got):
        counts, reduced = got
        groups: dict = {}
        for s, key in enumerate(slot_keys):
            c = int(counts[s])
            if c == 0:
                continue  # pyarrow's group_by returns the groups present
            vals = []
            for a, plan, want in zip(query.aggregates, plans, wants):
                if want is None:
                    vals.append(c)
                    continue
                i, k = where[want]
                v = _from_domain(reduced[i][k][s], plan.typ)
                vals.append((v, c) if a.op == "avg" else v)
            groups[key] = vals
        types = [None if w is None else plan.reduced for plan, w in zip(plans, wants)]
        return groups, types

    return fetch, finish
