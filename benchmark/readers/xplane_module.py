"""Device time of one jitted program by its name (lib/xmodules.py: summed
duration, inside the window, of the first device's "XLA Modules" events named
`module`), over the metric's denominator: the whole kernel, the ops XLA leaves
without a scope included. Nothing on a run that was not traced on a chip, or
where the program never ran (a checkout without the kernel)."""

from per import scaled
from xmodules import module_seconds


def read(obs, module, per):
    seconds = None if obs.xplane is None else module_seconds(module)
    return None if seconds is None else scaled(obs, seconds, per)
