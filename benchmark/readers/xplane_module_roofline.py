"""A kernel's share of its memory roofline, in %, with the kernel read whole
by its program's name: the bytes its algorithm has to move (a function of
lib/<module>.py, fed the rises of the program's own counters over the window)
over the device time of the jitted program `module` (lib/xmodules.py) and the
chip's published peak (lib/peaks.py). xplane_scope_roofline's sibling for a
kernel whose 64-bit inputs XLA splits outside every scope: a share taken over
the scoped ops alone would leave out part of the work and pass 100 %. Nothing
on a run that was not traced on a chip, where the program never ran, or where
a counter did not rise."""

import importlib

from xmodules import module_seconds


def read(obs, module, bytes_of, counters, peak):
    seconds = None if obs.xplane is None else module_seconds(module)
    counts = {arg: obs.counters.get(key) for arg, key in counters.items()}
    if not seconds or not all(counts.values()):
        return None
    import jax

    from peaks import peaks_for

    lib, _, function = bytes_of.partition(".")
    nbytes = getattr(importlib.import_module(lib), function)(**counts)  # benchmark/lib is on sys.path
    return 100.0 * nbytes / (seconds * peaks_for(jax.devices()[0].device_kind)[peak])
