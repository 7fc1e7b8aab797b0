"""Seeded queries from a cell's template: what the two query traffic kinds
share, so that another query shape is another cell file and no code.

The cell's file gives `filters` and `aggregates` as the daemon takes them, with
"$name" where a value is drawn per query, and `draw`: {name: "zone" (a taxi
zone, with the corpus's own pickup skew: the corpus kind's `ZONES` and
`zone_weights`) | [choices] (uniform)}. Queries are distinct; `files_for(i)`
says which files query i scans.
"""

from __future__ import annotations


def make_queries(ctx, n: int, files_for) -> list:
    import numpy as np

    zones, zone_weights = ctx.corpus_kind.ZONES, ctx.corpus_kind.zone_weights

    rng = np.random.default_rng([ctx.seed, 2])
    cell = ctx.cell

    def draw(spec):
        if spec == "zone":
            return int(rng.choice(zones, p=zone_weights())) + 1
        return spec[int(rng.integers(len(spec)))]

    seen, out = set(), []
    while len(out) < n:
        files = files_for(len(out))
        values = {"$" + name: draw(spec) for name, spec in cell["draw"].items()}
        key = (tuple(files), tuple(sorted(values.items())))
        if key in seen:
            continue
        seen.add(key)
        out.append({
            "files": files,
            "filters": [[c, op, values.get(v, v)] for c, op, v in cell["filters"]],
            "aggregates": cell["aggregates"],
        })
    return out
