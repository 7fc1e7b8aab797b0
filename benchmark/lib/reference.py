"""The plain reference: pyarrow's answers to the queries a serve cell sends.

A query is {"files": [file indices], "filters": [[column, op, value], ...]
(one conjunction; a null never passes), "aggregates": ["count" |
[fn, column], ...]} with fn in sum/min/max. Each corpus worker answers its own
file's share from the table it holds (`partial_answers`); `merge` folds the
shares into the one result the daemon must return, under the daemon's own
result keys ("count", "sum(passenger_count)", ...). Host only: never jax.
"""

from __future__ import annotations

_OPS = {"==": "equal", "!=": "not_equal", "<": "less", "<=": "less_equal",
        ">": "greater", ">=": "greater_equal"}


def agg_key(agg) -> str:
    return agg if isinstance(agg, str) else f"{agg[0]}({agg[1]})"


def answer(table, query: dict) -> dict:
    import pyarrow.compute as pc

    keep = None
    for column, op, value in query["filters"]:
        m = pc.fill_null(getattr(pc, _OPS[op])(table[column], value), False)
        keep = m if keep is None else pc.and_(keep, m)
    t = table if keep is None else table.filter(keep)
    out = {}
    for agg in query["aggregates"]:
        if agg == "count":
            out["count"] = t.num_rows
        else:
            out[agg_key(agg)] = getattr(pc, agg[0])(t[agg[1]]).as_py()
    return out


def partial_answers(table, name: str, queries: list) -> list:
    """This file's share of each query (None where the query skips it)."""
    index = int(name.rsplit("-", 1)[1].split(".")[0]) - 1
    return [answer(table, q) if index in q["files"] else None for q in queries]


def merge(shares: list) -> dict:
    out: dict = {}
    for share in shares:
        for key, v in share.items():
            if v is None:
                out.setdefault(key, None)
            elif out.get(key) is None:
                out[key] = v
            elif key.startswith("min("):
                out[key] = min(out[key], v)
            elif key.startswith("max("):
                out[key] = max(out[key], v)
            else:  # count, sum
                out[key] += v
    return out


def expected(facts: dict, n_queries: int) -> list:
    """One merged answer per query, from the corpus facts."""
    return [
        merge([f["partials"][q] for f in facts["files"] if f["partials"][q] is not None])
        for q in range(n_queries)
    ]


def request_body(query: dict, names: list) -> dict:
    """The /v1/query body for a query (paths relative to the daemon's root)."""
    return {
        "paths": [names[i] for i in query["files"]],
        "filters": [[list(f) for f in query["filters"]]],
        "aggregates": query["aggregates"],
    }
