"""Device busy time from the profiler trace (lib/xplane.py: the union of the
intervals in which an operation ran on the device, inside the window), over
the metric's denominator. Nothing on a run that was not traced on a chip."""

from per import scaled


def read(obs, per):
    if obs.xplane is None or obs.xplane["busy_s"] <= 0:
        return None
    return scaled(obs, obs.xplane["busy_s"], per)
