"""A cell's corpus, written once per (spec, seed, queries) by worker processes.

What is particular to a table lives in benchmark/corpora/<spec["kind"]>.py
(found by name, lib/byname.py), host only (numpy + pyarrow; never jax):

    file_name(index) -> str
    write_file(spec, seed, index, directory, queries) -> dict
        one file written, and what later comparisons need of it. The harness
        reads `index` and `rows`; the rest is the kind's and its traffic's.
    rehearsal(spec, rows) -> (spec, scale)
        the kind's own way to shrink itself for run.py --rehearse, and the
        factor by which the cell's `*_rows` shrink with it. Optional: a kind
        without it cannot be rehearsed.

Of the spec the harness reads `kind` and `files` (how many: indices 0..files-1);
every other key is the kind's own.
"""

from __future__ import annotations

import hashlib
import json
import shutil
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from pathlib import Path

from byname import load_by_name


def corpus_key(spec: dict, seed: int, queries: list) -> str:
    blob = json.dumps([spec, seed, queries], sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def write_one(spec: dict, seed: int, index: int, directory: str, queries: list) -> dict:
    """What a worker runs: a module loaded from a path cannot be pickled by
    name, so the spawned process loads the kind itself."""
    return load_by_name("corpora", spec["kind"]).write_file(spec, seed, index, directory, queries)


class CorpusJob:
    """The corpus being written by worker processes while the caller does
    other set-up (imports jax, builds the native library). `result()` waits.
    One corpus is kept per cache directory: another key replaces it, so a
    checkout never holds more than one corpus on disk."""

    def __init__(self, spec: dict, seed: int, queries: list, cache: Path, workers: int):
        self.kind = load_by_name("corpora", spec["kind"])  # an unknown kind ends the run before any worker starts
        self.dir = cache / "corpus"
        self.key = corpus_key(spec, seed, queries)
        self.pool = None
        done = self.dir / "DONE"
        if done.exists() and done.read_text().strip() == self.key:
            self.facts = json.loads((self.dir / "facts.json").read_text())
            self.reused = True
            return
        self.reused = False
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        self.pool = ProcessPoolExecutor(max_workers=workers, mp_context=get_context("spawn"))
        self.futures = [
            self.pool.submit(write_one, spec, seed, i, str(self.dir), queries)
            for i in range(spec["files"])
        ]

    def result(self) -> dict:
        if self.pool is not None:
            try:
                files = [f.result() for f in self.futures]
            finally:
                self.close()
            self.facts = {"files": files}
            (self.dir / "facts.json").write_text(json.dumps(self.facts))
            (self.dir / "DONE").write_text(self.key + "\n")
        self.facts["paths"] = [str(self.dir / self.kind.file_name(i)) for i in range(len(self.facts["files"]))]
        return self.facts

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=True, cancel_futures=True)
            self.pool = None
