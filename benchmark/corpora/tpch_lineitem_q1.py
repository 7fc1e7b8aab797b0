"""Corpus kind tpch_lineitem_q1: the table of corpus kind tpch_lineitem — its
write_file (generator, file names, writer options) and its rehearsal, called,
so the same (spec, seed, index) writes the same bytes — with each file's share
of every seeded Q1 (lib/reference_tpch_q1.py over pyarrow's read of the file
just written) where tpch_lineitem returns Q6's. A kind of its own because a
corpus kind is bound to its reference: tpch_lineitem.write_file imports Q6's.

Host only (numpy + pyarrow; never jax), so lib/corpus.py's workers can load it.
"""

from __future__ import annotations

from pathlib import Path

from byname import load_by_name  # benchmark/lib is on sys.path

_table = load_by_name("corpora", "tpch_lineitem")
build_table, file_name, rehearsal = _table.build_table, _table.file_name, _table.rehearsal


def write_file(spec: dict, seed: int, index: int, directory: str, queries: list) -> dict:
    """Write one file as tpch_lineitem writes it (asked for no Q6 share) and
    return what later comparisons need of it."""
    from reference_tpch_q1 import file_shares

    facts = _table.write_file(spec, seed, index, directory, [])
    return dict(facts, shares=file_shares(str(Path(directory) / file_name(index)), queries))
