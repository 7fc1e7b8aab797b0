"""benchmark/<kind>/<name>.py as a module: how a traffic kind, a reader kind
and a corpus kind are found, so that a new kind is a new file and an edit to
no file that is there. Host only: the corpus's worker processes load their
kind through it."""

from __future__ import annotations

import importlib.util
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def load_by_name(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no {kind} kind {name!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
