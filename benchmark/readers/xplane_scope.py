"""Device time under one of the program's named scopes (lib/xspans.py: summed
duration, inside the window, of the first device's ops whose scope path
contains `scope`), over the metric's denominator. Nothing on a run that was
not traced on a chip, or where no op carries a scope (a program without
named scopes, as before PR 26)."""

from per import scaled
from xspans import load, scope_seconds


def read(obs, scope, per):
    trace = None if obs.xplane is None else load()
    seconds = None if trace is None else scope_seconds(trace, scope)
    return None if seconds is None else scaled(obs, seconds, per)
