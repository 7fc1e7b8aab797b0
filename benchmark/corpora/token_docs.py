"""Corpus kind token_docs: a seeded tokenised web corpus, one document a row,
`input_ids: list<int32>`, as a pre-training job's tokenising stage leaves it.

Host only (numpy + pyarrow; never jax), so the worker processes of
lib/corpus.py can load it. What it draws from (seed, file index):

  lengths  lognormal(length_mu, length_sigma) clipped to [length_min,
           length_max]: heavy-tailed, mean ~1,030, median ~565;
  ids      a Zipf law (exponent zipf_exponent) over `vocabulary` ranks under a
           fixed permutation of the ids; the last id of every document is
           eos_id;
  groups   a row group closes at the last whole document that keeps it within
           row_group_tokens: a size-cut writer. So every group's token count,
           document count, run count and dictionary size is another number,
           and nothing here repeats from seed to seed. A budget that is a
           power of two keeps every group's token count in one power-of-two
           bucket (length_max is under half of it); that no other count of a
           group reaches a compiled shape is the program's to hold, and
           selftest/shapes_check_packed.py counts whether it does: the
           harness warms up one file and fails a window that compiles.

pyarrow's defaults otherwise (dictionary on, 1 MiB pages, V1 pages), one
write_table a row group. write_file returns `rows` (documents), the token
counts, and the file's reference digests at spec["seq_len"]
(lib/reference_packed.py: pack + digests of pyarrow's read of the file just
written — the reference reads what the program will read, not what the writer
held — taken here because the corpus's workers are processes of their own).
"""

from __future__ import annotations

from pathlib import Path

PERMUTATION_SEED = 50257  # the rank -> id permutation is the corpus's, not the run's


def zipf_cdf(spec: dict):
    import numpy as np

    w = 1.0 / np.arange(1, spec["vocabulary"] + 1, dtype=np.float64) ** spec["zipf_exponent"]
    return np.cumsum(w / w.sum())


def build_groups(spec: dict, seed: int, index: int):
    """File `index` as a list of (offsets int32[docs + 1], ids int32[tokens])
    row groups, from (seed, index)."""
    import numpy as np

    rng = np.random.default_rng([seed, index])
    budget = spec["row_group_tokens"]
    ids_of_rank = np.random.default_rng(PERMUTATION_SEED).permutation(spec["vocabulary"]).astype(np.int32)
    cdf = zipf_cdf(spec)
    mean = np.exp(spec["length_mu"] + spec["length_sigma"] ** 2 / 2)
    groups, pending = [], np.zeros(0, dtype=np.int64)
    while len(groups) < spec["row_groups_per_file"]:
        if pending.sum() <= budget:  # draw on: about a group and a half of documents more
            drawn = rng.lognormal(spec["length_mu"], spec["length_sigma"], int(1.5 * budget / mean) + 16)
            pending = np.concatenate(
                [pending, np.clip(drawn.astype(np.int64), spec["length_min"], spec["length_max"])])
            continue
        docs = int(np.searchsorted(np.cumsum(pending), budget, side="right"))  # whole documents within the budget
        lengths, pending = pending[:docs], pending[docs:]
        offsets = np.zeros(docs + 1, dtype=np.int32)
        np.cumsum(lengths, out=offsets[1:])
        ranks = np.searchsorted(cdf, rng.random(int(offsets[-1])), side="right")
        ids = ids_of_rank[np.minimum(ranks, spec["vocabulary"] - 1)]
        ids[offsets[1:] - 1] = spec["eos_id"]
        groups.append((offsets, ids))
    return groups


def file_name(index: int) -> str:
    return f"tokens-{index:05d}-of-train.parquet"


def write_file(spec: dict, seed: int, index: int, directory: str, queries: list) -> dict:
    """Write one file and return what later comparisons need of it."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from reference_packed import digests, pack  # benchmark/lib is on sys.path

    schema = pa.schema([("input_ids", pa.list_(pa.int32()))])
    arrays = [pa.ListArray.from_arrays(pa.array(offsets), pa.array(ids))
              for offsets, ids in build_groups(spec, seed, index)]
    path = str(Path(directory) / file_name(index))
    with pq.ParquetWriter(path, schema, compression=spec["compression"]) as writer:
        for a in arrays:  # one write_table, one row group
            writer.write_table(pa.Table.from_arrays([a], schema=schema), row_group_size=len(a))
    return {
        "index": index, "rows": sum(len(a) for a in arrays),
        "tokens": sum(len(a.values) for a in arrays), "group_tokens": [len(a.values) for a in arrays],
        "seq_len": spec["seq_len"],
        "digests": digests(*pack(pq.read_table(path)["input_ids"], spec["seq_len"])),
    }


def rehearsal(spec: dict, rows: int) -> tuple:
    """The corpus at `rows` x 16 tokens a row group, three groups a file, no
    document over a quarter of a group, for a CPU rehearsal: (spec, scale)."""
    tokens = 16 * rows
    return dict(spec, row_group_tokens=tokens, row_groups_per_file=3,
                length_max=min(spec["length_max"], tokens // 4)), tokens / spec["row_group_tokens"]
