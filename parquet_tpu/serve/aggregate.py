"""Aggregation push-down: per-unit partials, exact merge, canonical body.

The serve bench measured the daemon serialization-bound: a dashboard-style
"how many rows match, grouped by X" question paid for boxing and shipping
every matching row. This module answers it server-side: each unit (one row
group of one file) computes a PARTIAL aggregate over its filtered arrow
table on the pqt-serve pool, partials merge with exact semantics, and the
response is kilobytes regardless of how many rows matched.

Semantics are PINNED AGAINST PYARROW by construction, not by reimplementation:
unit partials are pyarrow.compute kernels (count/sum/min/max and
TableGroupBy for group-by), and merging a key's partial values runs the same
kernel over the array of them OF THE PARTIAL'S ARROW TYPE (QueryState files
a unit's values as they arrive, in unit order, and folds a key's _FOLD_AT at
a time and once more when the body is built: one kernel call an aggregate,
not one a pair — an Arrow call hands the GIL away, and the thread that
merges is the one that feeds the unit pool) — so null
skipping (sum/min/max ignore nulls, all-null yields null), NaN propagation
(sum) vs NaN skipping (min/max), decimal precision, and int64 wraparound
all come out identical to a single whole-corpus pyarrow aggregation
(differential tests assert exactly that).

avg is the one op pyarrow's kernels do not pin, because pyarrow averages in
float: a unit's partial for avg(x) is the exact pair (sum of x in sum's own
Arrow domain, count of non-null x), pairs merge by adding both halves, and
the quotient is taken ONCE per group per query, when the body is rendered
(render_avg): a decimal of x's scale + 4, rounded half up (Spark's rule for
avg over DECIMAL(p, s): DECIMAL(p + 4, s + 4); an integer x has scale 0),
as text, in integer arithmetic. avg over zero non-null inputs is null; avg
over a float input is a typed 400 — no float on either lane.

Group-by cardinality is BOUNDED: the merged table growing past the
request's max_groups raises the typed overflow ServeError (413
group_overflow) instead of buffering an unbounded result — push-down must
not become a memory vector.

The canonical JSON rendering lives here too (render_query_body): the
daemon's POST /v1/query response and `parquet-tool scan --aggregate`
output are the SAME bytes for the same corpus and spec, like the
jsonl-scan contract protocol.py pins for rows.
"""

from __future__ import annotations

import decimal
import json

from . import expr as _expr
from .protocol import QueryRequest, ServeError, agg_name, json_default

__all__ = [
    "QueryState",
    "query_columns",
    "unit_partial",
    "unit_count_partial",
    "result_dict",
    "render_avg",
    "render_query_body",
    "run_local_query",
]


def query_columns(query: QueryRequest) -> list:
    """The column projection a query's units must decode: group-by keys
    plus aggregate inputs, order-stable. Empty + no filters means NO decode
    at all (pure count(*) answers from footer-promised row counts); empty
    WITH filters borrows the first filter column so the filtered row count
    is still observable."""
    cols: list = []
    for c in query.group_by:
        if c not in cols:
            cols.append(c)
    for a in query.aggregates:
        for c in agg_inputs(a):
            if c not in cols:
                cols.append(c)
    if not cols and query.filters is not None:
        first = query.filters[0]
        if isinstance(first, (list, tuple)) and first and isinstance(
            first[0], (list, tuple)
        ):
            first = first[0]  # DNF: first conjunction's first triple
        cols.append(first[0])
    return cols


def agg_inputs(a) -> list:
    """The columns one aggregate reads: its column, or its expression's."""
    if a.expr is not None:
        return _expr.columns(a.expr)
    return [] if a.column is None else [a.column]


def _agg_input(table, a):
    """One aggregate's input over a unit's filtered table: the column, or
    its expression evaluated by pyarrow.compute (serve/expr.py)."""
    import pyarrow as pa

    if a.expr is None:
        return _agg_column(table, a.column)
    try:
        return _expr.evaluate(a.expr, lambda name: _agg_column(table, name))
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
        raise ServeError(
            400, "bad_aggregates", f"cannot evaluate {a.column!r}: {e}"
        ) from None


def _agg_column(table, name: str):
    import pyarrow.compute as pc

    parts = name.split(".")
    try:
        col = table.column(parts[0])
    except KeyError:
        raise ServeError(
            400, "bad_aggregates", f"aggregate column {name!r} not in scan"
        ) from None
    for p in parts[1:]:
        col = pc.struct_field(col, p)
    return col


def unit_partial(table, query: QueryRequest):
    """(groups, types) partial of one unit's filtered arrow table:
    groups maps key tuple -> [one python value per aggregate] (the global
    form uses the () key); types carries each aggregate's arrow type so
    merges run in the exact same domain."""
    import pyarrow as pa
    import pyarrow.compute as pc

    aggs = query.aggregates
    if not query.group_by:
        vals: list = []
        types: list = [None] * len(aggs)
        for j, a in enumerate(aggs):
            if a.column is None:
                vals.append(table.num_rows)
                continue
            col = _agg_input(table, a)
            try:
                if a.op == "count":
                    vals.append(int(pc.count(col).as_py()))
                    continue
                fn = {"sum": pc.sum, "min": pc.min, "max": pc.max, "avg": pc.sum}
                s = fn[a.op](col)
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
                raise ServeError(
                    400, "bad_aggregates",
                    f"cannot {a.op} column {a.column!r}: {e}",
                ) from None
            types[j] = s.type
            if a.op == "avg":
                _check_avg_domain(a, s.type)
                vals.append(avg_pair(s.as_py(), int(pc.count(col).as_py())))
            else:
                vals.append(s.as_py())
        return {(): vals}, types
    keys = list(query.group_by)
    spec = []
    at: list = []  # per aggregate: its first column in the spec
    for j, a in enumerate(aggs):
        at.append(len(spec))
        if a.column is None:
            spec.append(([], "count_all"))
            continue
        name = a.column
        if a.expr is not None:  # grouped as a column of its own
            name = f"__agg{j}"
            table = table.append_column(name, _agg_input(table, a))
        if a.op == "avg":  # the exact pair: its sum, then its count
            spec += [(name, "sum"), (name, "count")]
        else:
            spec.append((name, a.op))
    try:
        res = table.group_by(keys).aggregate(spec)
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError, KeyError) as e:
        raise ServeError(
            400, "bad_aggregates", f"cannot group by {keys}: {e}"
        ) from None
    if res.num_columns != len(keys) + len(spec):
        raise ServeError(
            500, "internal", "group-by result shape mismatch"
        )
    # pyarrow's aggregate table leads with the key columns, then the
    # aggregates in spec order — read positionally (names can collide)
    kl = [res.column(i).to_pylist() for i in range(len(keys))]
    cl = [res.column(len(keys) + i).to_pylist() for i in range(len(spec))]
    types: list = []
    al: list = []
    for j, a in enumerate(aggs):
        typ = res.column(len(keys) + at[j]).type
        types.append(None if a.op == "count" else typ)
        if a.op == "avg":
            _check_avg_domain(a, typ)
            al.append([avg_pair(s, n) for s, n in zip(cl[at[j]], cl[at[j] + 1])])
        else:
            al.append(cl[at[j]])
    groups = {}
    for g in range(res.num_rows):
        key = tuple(k[g] for k in kl)
        groups[key] = [a[g] for a in al]
    return groups, types


def _check_avg_domain(a, typ) -> None:
    import pyarrow as pa

    if not (pa.types.is_integer(typ) or pa.types.is_decimal(typ)):
        raise ServeError(
            400, "bad_aggregates",
            f"cannot avg column {a.column!r}: its sum is {typ}; avg is exact "
            "(integer and decimal inputs only)",
        )


def avg_pair(total, count: int):
    """avg's partial value: (sum, count of non-null inputs), or None where
    nothing was averaged."""
    return (total, count) if count else None


def render_avg(pair, typ):
    """The quotient of an avg pair as text, taken once: a decimal of the
    sum's scale + 4 (an integer sum has scale 0), rounded half up — away
    from zero at the tie — in integer arithmetic. None stays null."""
    if pair is None:
        return None
    total, count = pair
    scale = getattr(typ, "scale", 0) + 4
    if isinstance(total, int):
        unscaled = total * 10**scale
    else:  # by its digits: decimal's own arithmetic rounds at 28
        sign, digits, exponent = total.as_tuple()
        unscaled = int("".join(map(str, digits))) * 10 ** (exponent + scale)
        unscaled = -unscaled if sign else unscaled
    q = (2 * abs(unscaled) + count) // (2 * count)
    text = decimal.Decimal((int(unscaled < 0), tuple(map(int, str(q))), -scale))
    return format(text, "f")


def unit_count_partial(query: QueryRequest, num_rows: int):
    """The zero-decode partial: every aggregate is count(*) (query_columns
    returned empty with no filters), so the footer-promised row count IS
    the answer and the unit never opens its file."""
    return {(): [num_rows for _ in query.aggregates]}, [None] * len(
        query.aggregates
    )


def _merge_values(op: str, vals: list, typ):
    """One aggregate's partials folded into one: counts add, sum / min / max
    go through the Arrow kernel over the partials' own type (so the fold is
    pyarrow's, exactly), nulls skipped, null where every partial is null; an
    avg pair adds its two halves, the sum in its Arrow domain. ONE kernel
    call however many partials: a call a pair would hand the GIL away and
    wait for it again a thousand times a grouped query, on the one thread
    that also feeds the unit pool."""
    if op == "count":
        return sum(int(v) for v in vals)
    live = [v for v in vals if v is not None]
    if len(live) < 2:
        return live[0] if live else None
    if op == "avg":
        return (
            _merge_values("sum", [p[0] for p in live], typ),
            sum(p[1] for p in live),
        )
    import pyarrow as pa
    import pyarrow.compute as pc

    arr = pa.array(live, type=typ)
    if op == "sum":
        return pc.sum(arr).as_py()
    if op == "min":
        return pc.min(arr).as_py()
    return pc.max(arr).as_py()


# partials a key may hold of one aggregate before they are folded: bounds
# the state of a query over many units at groups x aggregates x this
_FOLD_AT = 64


class QueryState:
    """The merged aggregate state one request accumulates unit by unit.
    `absorb` only files a unit's values under their key (plain list work,
    no Arrow call); they are folded by `_merge_values` when a key holds
    _FOLD_AT of them and when `groups` is read."""

    __slots__ = ("query", "_pending", "types", "rows_scanned", "rows_matched")

    def __init__(self, query: QueryRequest):
        self.query = query
        self.types: list = [None] * len(query.aggregates)
        self.rows_scanned = 0
        self.rows_matched = 0
        # key -> per aggregate, the list of partials not folded yet
        self._pending: dict = {}
        if not query.group_by:
            # the global row exists even over zero units: count 0, sum/min/
            # max null — matching pyarrow kernels over an empty column
            self._pending[()] = [
                [0 if a.column is None or a.op == "count" else None]
                for a in query.aggregates
            ]

    def absorb(self, part) -> None:
        """Merge one unit's ((groups, types), scanned, matched) partial."""
        (groups, types), scanned, matched = part
        self.rows_scanned += scanned
        self.rows_matched += matched
        for j, t in enumerate(types):
            if self.types[j] is None:
                self.types[j] = t
        q = self.query
        for key, vals in groups.items():
            cur = self._pending.get(key)
            if cur is None:
                if len(self._pending) >= q.max_groups:
                    raise ServeError(
                        413, "group_overflow",
                        f"group-by cardinality exceeded max_groups="
                        f"{q.max_groups}; narrow the filter or raise "
                        "max_groups",
                    )
                self._pending[key] = [[v] for v in vals]
                continue
            for held, v in zip(cur, vals):
                held.append(v)
            if len(cur[0]) >= _FOLD_AT:  # aggregates are never empty (protocol.py)
                self._fold(cur)

    def _fold(self, cur: list) -> None:
        for j, a in enumerate(self.query.aggregates):
            if len(cur[j]) > 1:
                op = "count" if a.column is None else a.op
                cur[j] = [_merge_values(op, cur[j], self.types[j])]

    @property
    def groups(self) -> dict:
        """key -> the merged value of every aggregate."""
        for cur in self._pending.values():
            self._fold(cur)
        return {key: [held[0] for held in cur] for key, cur in self._pending.items()}


def _key_order(key: tuple) -> str:
    # deterministic total order over arbitrary (possibly None/mixed) keys:
    # their canonical JSON encoding — the same bytes the body renders
    return json.dumps(list(key), default=json_default)


def result_dict(query: QueryRequest, state: QueryState, *, units: int) -> dict:
    """The response body, deterministically ordered (groups sort by their
    canonical key encoding) so daemon bytes == CLI bytes."""
    names = [agg_name(a) for a in query.aggregates]

    def rendered(vals: list) -> dict:
        return {
            name: render_avg(v, typ) if a.op == "avg" else v
            for name, a, v, typ in zip(names, query.aggregates, vals, state.types)
        }

    body: dict = {
        "group_by": list(query.group_by),
        "aggregates": names,
        "units": units,
        "rows_scanned": state.rows_scanned,
        "rows_matched": state.rows_matched,
    }
    groups = state.groups
    if query.group_by:
        body["group_count"] = len(groups)
        body["groups"] = [
            {"key": list(key), "aggregates": rendered(groups[key])}
            for key in sorted(groups, key=_key_order)
        ]
    else:
        body["result"] = rendered(groups[()])
    return body


def render_query_body(body: dict) -> bytes:
    """ONE canonical serialization (shared with `parquet-tool scan
    --aggregate`), so a daemon response is byte-identical to the CLI's."""
    return (json.dumps(body, default=json_default) + "\n").encode()


def run_local_query(paths, query: QueryRequest, *, footer_cache=None) -> dict:
    """The daemon-free twin of POST /v1/query: plan, execute every unit
    sequentially, merge — `parquet-tool scan --aggregate` and the parity
    tests run the daemon's exact semantics against local files."""
    from ..core.reader import FileReader
    from ..data.plan import build_plan, expand_paths

    files: list = []
    for p in paths:
        files.extend(expand_paths(p))
    files = sorted(set(files))
    plan = build_plan(files, filters=query.filters, footer_cache=footer_cache)
    if query.shard is not None:
        order = plan.epoch_order(
            0, shard_index=query.shard[0], shard_count=query.shard[1]
        )
        units = [plan.units[k] for k in order]
    else:
        units = list(plan.units)
    cols = query_columns(query)
    decode = bool(cols) or query.filters is not None
    state = QueryState(query)
    for u in units:
        if not decode:
            state.absorb(
                (unit_count_partial(query, u.num_rows), u.num_rows, u.num_rows)
            )
            continue
        meta = plan.metas[u.file_index]
        with FileReader(u.path, columns=cols or None, metadata=meta) as r:
            t = r.to_arrow(row_groups=[u.row_group], filters=query.filters)
        state.absorb((unit_partial(t, query), u.num_rows, t.num_rows))
    return result_dict(query, state, units=len(units))
