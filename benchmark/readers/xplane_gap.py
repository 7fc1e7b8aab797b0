"""Idle time of the device that goes to the named labels (lib/xspans.py: each
gap of the first device inside the window goes to the first of
dispatch.upload, dispatch.launch, chunk.prepare, io.read, deliver whose
"pqt:" annotation is open on any thread at the gap's midpoint, else to
"none"), over the metric's denominator. Nothing on a run that was not traced
on a chip, or where the program wrote no annotation (as before PR 26)."""

from per import scaled
from xspans import load, gap_seconds


def read(obs, labels, per):
    trace = None if obs.xplane is None else load()
    gaps = None if trace is None else gap_seconds(trace)
    return None if gaps is None else scaled(obs, sum(gaps[label] for label in labels), per)
