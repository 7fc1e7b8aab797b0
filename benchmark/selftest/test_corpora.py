"""A corpus is found by name, and moving the trip record's writer moved nothing.

    python -m pytest benchmark/selftest/test_corpora.py -q        (CPU, host only, seconds)

What ISSUE 32 asked for as tests/test_benchmark_corpora.py: a benchmark PR may
add files only under the benchmark's own directory, so it lives here until a
later PR gives tier-1 a door to it (PERF.md section 7).
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH / "lib"), str(HERE)]

from byname import load_by_name  # noqa: E402
from corpus import CorpusJob, corpus_key  # noqa: E402
from runner import run_cell  # noqa: E402

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
QUERY = [{"files": [0, 5], "filters": [["PULocationID", "==", 132]], "aggregates": ["count"]}]


def corpus_of(config: str) -> dict:
    return json.loads((BENCH / "configs" / f"{config}.json").read_text())["corpus"]


@pytest.mark.parametrize("config", ["tlc-year-stream", "tlc-year-wide", "tlc-year-serve"])
def test_corpus_key_is_the_parents(config):
    """Literals taken with PR 31's lib/corpus.py: a cached corpus is reused
    across the move, and nothing inside a `corpus` object was touched."""
    spec = corpus_of(config)
    assert corpus_key(spec, 7, []) == "093628680cb7c6c1"
    assert corpus_key(spec, 3000000019, QUERY) == "8d277a75c25dd843"


@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_names_a_kind_that_is_there(config):
    kind = load_by_name("corpora", corpus_of(config)["kind"])
    for name in ("file_name", "write_file", "rehearsal"):
        assert callable(getattr(kind, name)), f"{config}: corpora/{corpus_of(config)['kind']}.py lacks {name}"


def test_the_harness_and_the_job_know_no_table():
    for path in (BENCH / "run.py", BENCH / "lib" / "corpus.py"):
        text = path.read_text().lower()
        for word in ("tlc", "nulls_per_group", "sum_rows", "rows_per_file"):
            assert word not in text, f"{path.name} says {word!r}"


def test_rehearsal_scales_the_year_as_run_py_did():
    spec, scale = load_by_name("corpora", "tlc_yellow_2023").rehearsal(corpus_of("tlc-year-wide"), 16384)
    assert scale == 1 / 64
    assert spec == dict(corpus_of("tlc-year-wide"), row_group_rows=16384, rows_per_file=49152,
                        nulls_per_group=655, sum_rows=1024)


def test_unknown_kind_ends_before_any_worker(tmp_path):
    with pytest.raises(SystemExit) as e:
        CorpusJob({"kind": "no_such_table", "files": 2}, 7, [], tmp_path, workers=2)
    assert str(BENCH / "corpora" / "no_such_table.py") in str(e.value)
    assert not (tmp_path / "corpus").exists()


def test_moved_writer_writes_the_table_build_table_gives(tmp_path):
    import pyarrow.parquet as pq

    kind = load_by_name("corpora", "tlc_yellow_2023")
    spec, _ = kind.rehearsal(corpus_of("tlc-year-wide"), 4096)
    facts = kind.write_file(spec, 2147483777, 3, str(tmp_path), [])
    back = pq.read_table(tmp_path / kind.file_name(3))
    assert back.equals(kind.build_table(spec, 2147483777, 3))
    assert (facts["index"], facts["rows"]) == (3, 3 * 4096) and back.column_names == list(kind.COLUMNS)
    meta = pq.ParquetFile(tmp_path / kind.file_name(3)).metadata
    assert meta.num_row_groups == 3 and "DELTA_BINARY_PACKED" in meta.row_group(0).column(1).encodings


# -- a scratch tree that grows by new files only ---------------------------------

DUMMY_CONFIG = json.loads((HERE / "dummy" / "dummy-config.json").read_text())


@pytest.fixture()
def tree(tmp_path):
    """The benchmark's files copied, plus a cell over configuration
    dummy-config; the test adds (or does not add) the corpus kind."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__", "records"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="dummy-config",
                                 file="benchmark/configs/dummy-config.json"))
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy-config", "traffic": "stream_reader",
                               "chips": 1, "why": "self-test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(HERE / "dummy" / "dummy-config.json", tmp_path / "benchmark/configs")
    shutil.copy(HERE / "dummy" / "dummy.cell.json", tmp_path / "benchmark/workloads")
    return tmp_path


def rehearse(tree: Path):
    return run_cell("dummy.cell", 7, 1, 0, ["--rehearse", "4096"], root=tree, env={"JAX_PLATFORMS": "cpu"})


def test_run_py_names_the_missing_kind(tree):
    rc, lines, _, err = rehearse(tree)
    assert rc != 0 and not lines
    assert str(tree / "benchmark/corpora/parts_uneven.py") in err and "is missing" in err


def test_a_kind_without_rehearsal_cannot_be_rehearsed(tree):
    source = (HERE / "dummy" / "parts_uneven.py").read_text()
    (tree / "benchmark/corpora/parts_uneven.py").write_text(source.split("def rehearsal")[0])
    rc, lines, _, err = rehearse(tree)
    assert rc != 0 and not lines
    assert "has no rehearsal(spec, rows)" in err


WRITE = """
import json, sys
sys.path.insert(0, "benchmark/lib")
from pathlib import Path
from byname import load_by_name
from corpus import CorpusJob
if __name__ == "__main__":
    spec = json.loads(Path("benchmark/configs/dummy-config.json").read_text())["corpus"]
    spec, _ = load_by_name("corpora", spec["kind"]).rehearsal(spec, 4096)
    job = CorpusJob(spec, 11, [], Path("cache").resolve(), workers=3)
    try:
        print(json.dumps(job.result()))
    finally:
        job.close()
"""


def test_a_kind_that_is_only_a_new_file_is_written_by_spawn_workers(tree):
    import pyarrow.parquet as pq

    shutil.copy(HERE / "dummy" / "parts_uneven.py", tree / "benchmark/corpora/parts_uneven.py")
    (tree / "write.py").write_text(WRITE)
    p = subprocess.run([sys.executable, "write.py"], cwd=tree, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    facts = json.loads(p.stdout.strip().splitlines()[-1])
    assert [f["rows"] for f in facts["files"]] == [6144, 4096, 9216]  # files of unequal row counts
    assert [Path(q).name for q in facts["paths"]] == [f"parts-{i:03d}.parquet" for i in range(3)]
    for f, path in zip(facts["files"], facts["paths"]):
        table = pq.read_table(path)
        assert table.num_rows == f["rows"] and table.column_names == DUMMY_CONFIG["delivered_columns"]
        assert table["quantity"].null_count == f["nulls"]["quantity"] > 0
    spec = dict(DUMMY_CONFIG["corpus"], row_group_rows=4096)
    assert (tree / "cache/corpus/DONE").read_text().strip() == corpus_key(spec, 11, [])
