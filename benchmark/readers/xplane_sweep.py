"""Idle time of the device that goes to the named labels of lib/xsweep.py's
order (every gap of the first device inside the window goes to ONE label: the
first of the order open on any thread at the gap's midpoint, else "outside"),
over the metric's denominator. A label that never opens reads 0. One sweep a
run, shared by every metric of the family. Nothing on a run that was not
traced on a chip, or where the program wrote no annotation at all."""

from per import scaled
from xsweep import gaps


def read(obs, labels, per):
    found = None if obs.xplane is None else gaps()
    return None if found is None else scaled(obs, sum(found[label] for label in labels), per)
