"""Device seconds by program: the "XLA Modules" line of the first device, where
every run of a jitted program is one event named `jit_<function>(<id>)` that
spans all of the program's ops — those that carry one of the program's named
scopes and those that cannot: on a TPU XLA splits every 64-bit parameter into
32-bit halves before anything else (custom calls X64SplitLow / X64SplitHigh),
and those ops take their metadata from the parameter, not from the scope the
kernel was traced under. A kernel whose inputs are int64 columns is therefore
read whole by its program's name (breakdown.device_ops sums the same events,
and keeps the ten longest); lib/xspans.py reads what a scope covers.

`load()` opens the newest trace run.py wrote, once per run.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

from xplane import clip, newest_xplane, read_trace

TRACE_DIR = Path(__file__).resolve().parent.parent / ".cache" / "trace"


@functools.lru_cache(maxsize=1)
def load(directory: Path = TRACE_DIR):
    """{program name: seconds inside the window} of the first device that ran
    anything; None where there is no trace, no window or no such line."""
    pb = newest_xplane(directory) if Path(directory).is_dir() else None
    if pb is None:
        return None
    devices, window = read_trace(pb)
    first = min((name for name, lines in devices.items() if lines["modules"]), default=None)
    if window is None or first is None:
        return None
    out: dict = {}
    for name, s, e in devices[first]["modules"]:
        for lo, hi in clip([(s, e)], *window):
            key = re.sub(r"\(\d+\)$", "", name)
            out[key] = out.get(key, 0.0) + (hi - lo) / 1e9
    return out


def module_seconds(name: str):
    """Seconds the program `name` ran inside the window; None where the trace
    holds no run of it (a program without the kernel)."""
    modules = load()
    return None if modules is None else modules.get(name)
