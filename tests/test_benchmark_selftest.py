"""Tier-1's door to the benchmark's own self-tests, and the corpus-kind tests
of token_docs.

A `benchmark` PR may add files only under benchmark/, so two of its test files
live there (PERF.md section 7) and tier-1, which collects tests/ only, never
ran them: benchmark/selftest/test_corpora.py (a corpus is found by name),
benchmark/selftest/test_faults_packed.py (`correct` is false exactly when
something is planted in the packed cell), and the TPC-H table's
test_corpora_tpch.py (dbgen's laws hold) and test_faults_tpch.py (the Q6
cell's faults), test_xsweep.py (the one-pass reduction of the device's
idle gaps, PR 37), and the Q1 deployment's test_corpora_tpch_q1.py (the same
bytes as tpch_lineitem; reference = second witness) and test_faults_tpch_q1.py
(the grouped cell's faults; PR 39). They are loaded by path and their tests, with the fixtures
they use, collected here under their own names.
"""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


FIXTURES = ("tree", "columns")  # test_corpora.py's scratch copy of the benchmark; the TPC-H file's arrays
for _name in ("test_corpora", "test_faults_packed", "test_corpora_tpch", "test_faults_tpch", "test_xsweep",
              "test_corpora_tpch_q1", "test_faults_tpch_q1"):
    _module = _load(BENCH / "selftest" / f"{_name}.py")
    globals().update({k: v for k, v in vars(_module).items() if k.startswith("test_") or k in FIXTURES})

# -- the corpus kind token_docs ---------------------------------------------------

sys.path.insert(0, str(BENCH / "lib"))  # the kind imports reference_packed as the corpus's workers do
token_docs = _load(BENCH / "corpora" / "token_docs.py")
reference = _load(BENCH / "lib" / "reference_packed.py")
SPEC = json.loads((BENCH / "configs" / "token-corpus-8k.json").read_text())["corpus"]
SMALL, SCALE = token_docs.rehearsal(SPEC, 1024)


def test_rehearsal_shrinks_the_corpus_and_nothing_else():
    assert SCALE == 16 * 1024 / SPEC["row_group_tokens"]
    assert SMALL == dict(SPEC, row_group_tokens=16384, row_groups_per_file=3, length_max=4096)


def test_the_same_seed_writes_the_same_bytes(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    facts = [token_docs.write_file(SMALL, 2147483777, 5, str(tmp_path / d), []) for d in "ab"]
    assert facts[0] == facts[1]
    name = token_docs.file_name(5)
    assert (tmp_path / "a" / name).read_bytes() == (tmp_path / "b" / name).read_bytes()
    other = token_docs.write_file(SMALL, 2147483778, 5, str(tmp_path / "b"), [])
    assert other["digests"] != facts[0]["digests"]


def test_every_group_is_within_its_token_budget(tmp_path):
    import pyarrow.parquet as pq

    facts = token_docs.write_file(SMALL, 7, 0, str(tmp_path), [])
    meta = pq.ParquetFile(tmp_path / token_docs.file_name(0)).metadata
    tokens = [meta.row_group(g).column(0).num_values for g in range(meta.num_row_groups)]
    assert tokens == facts["group_tokens"] and meta.num_row_groups == SMALL["row_groups_per_file"]
    assert len(set(tokens)) == len(tokens), "every group another count"
    # within the budget, and closed only because the next whole document would not fit
    assert all(SMALL["row_group_tokens"] - SMALL["length_max"] < t <= SMALL["row_group_tokens"] for t in tokens)
    assert (facts["rows"], facts["tokens"]) == (meta.num_rows, sum(tokens))


def test_documents_follow_the_stated_laws(tmp_path):
    groups = token_docs.build_groups(SMALL, 11, 2)
    lengths = np.concatenate([np.diff(offsets) for offsets, _ in groups])
    ids = np.concatenate([i for _, i in groups])
    assert lengths.min() >= SMALL["length_min"] and lengths.max() <= SMALL["length_max"]
    assert 0 <= ids.min() and ids.max() < SMALL["vocabulary"]
    for offsets, i in groups:
        assert (i[offsets[1:] - 1] == SMALL["eos_id"]).all(), "every document ends in its EOS"
    top = np.bincount(ids, minlength=SMALL["vocabulary"]).argmax()
    assert top in (SMALL["eos_id"], np.random.default_rng(token_docs.PERMUTATION_SEED).permutation(
        SMALL["vocabulary"])[0]), "the most frequent id is the EOS or the permutation's rank-1 id"


def test_the_facts_digests_are_the_references(tmp_path):
    import pyarrow.parquet as pq

    facts = token_docs.write_file(SMALL, 3000000019, 1, str(tmp_path), [])
    table = pq.read_table(tmp_path / token_docs.file_name(1))
    assert table.schema.field("input_ids").type.value_type == "int32"
    packed = reference.pack(table["input_ids"], SMALL["seq_len"])
    assert facts["digests"] == reference.digests(*packed) and facts["seq_len"] == SMALL["seq_len"]
    assert facts["digests"]["sequences"] == -(-facts["tokens"] // SMALL["seq_len"])
    # the digests tell a swap of two neighbouring tokens, and a lost boundary
    tokens, segment_ids, positions = (a.copy() for a in packed)
    j = int(np.flatnonzero(tokens[0, :-1] != tokens[0, 1:])[0])
    tokens[0, [j, j + 1]] = tokens[0, [j + 1, j]]
    swapped = reference.digests(tokens, segment_ids, positions)
    assert swapped["tokens"] == facts["digests"]["tokens"]
    assert swapped["tokens_weighted"] != facts["digests"]["tokens_weighted"]
    assert reference.digests(packed[0], np.minimum(segment_ids, 1), positions) != facts["digests"]
    # two whole sequences changing places leave every sum over slots, and tell in the three by sequence
    order = np.arange(len(packed[0]))
    order[[0, 1]] = [1, 0]
    moved = reference.digests(*(a[order] for a in packed))
    by_sequence = {k for k in moved if k.endswith("_by_sequence")}
    assert len(by_sequence) == 3 and {k for k in moved if moved[k] != facts["digests"][k]} == by_sequence
    # a file's digests are the wrapped sums of its parts', each told where in the file it starts
    cut = len(order) // 2
    parts = [reference.digests(*(a[:cut] for a in packed)),
             reference.digests(*(a[cut:] for a in packed), first_sequence=cut)]
    assert {k: (parts[0][k] + parts[1][k]) & reference.MASK64 for k in moved if k != "sequences"} == {
        k: v for k, v in facts["digests"].items() if k != "sequences"}
