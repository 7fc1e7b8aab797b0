"""A kernel's share of its memory roofline, in %: the bytes its algorithm has
to move (a function of lib/<module>.py, fed the rises of the program's own
counters over the window) over the device time under its named scope
(lib/xspans.py scope_seconds) and the chip's published peak (lib/peaks.py,
keyed by the device kind jax reports; an unknown kind is an error). Nothing on
a run that was not traced on a chip, where no op carries the scope (a program
without the kernel), or where a counter did not rise."""

import importlib

from xspans import load, scope_seconds


def read(obs, scope, bytes_of, counters, peak):
    trace = None if obs.xplane is None else load()
    seconds = None if trace is None else scope_seconds(trace, scope)
    counts = {arg: obs.counters.get(key) for arg, key in counters.items()}
    if not seconds or not all(counts.values()):
        return None
    import jax

    from peaks import peaks_for

    module, _, function = bytes_of.partition(".")
    nbytes = getattr(importlib.import_module(module), function)(**counts)  # benchmark/lib is on sys.path
    return 100.0 * nbytes / (seconds * peaks_for(jax.devices()[0].device_kind)[peak])
