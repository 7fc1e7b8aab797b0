"""The program's own names in the profiler trace (.xplane.pb): the named
scopes its kernels trace under, and the "pqt:<name>" annotations its
stage()/span() calls hold open (parquet_tpu/utils/trace.py). One trace plane:
device ops and host spans sit on the profiler's clock, so nothing is shifted.

`load()` opens the newest trace run.py wrote, once per run; `extract` pulls
plain lists out of the serialized XSpace; `scope_seconds` is arithmetic on
those lists, checked on a hand-built trace (selftest/xspans_check.py), and
lib/xsweep.py puts the device's idle gaps down to the spans. The XSpace is
decoded here, from the protobuf wire format
(tsl/profiler/protobuf/xplane.proto): the scope path is a stat
of an op's event METADATA, which jax's ProfileData does not hand out (its
`event.stats` are the event's own), and no xplane_pb2 is installed.
Definitions:

  window  the "bench:window" annotation, as in lib/xplane.py;
  ops     the first device's "XLA Ops" events, each with its scope path: the
          HLO op_name metadata, e.g.
          jit(expand_hybrid_device)/pqt.hybrid_expand/find_run/gather, which
          the runtime writes into the stat SCOPE_STATS names. XLA fuses
          across scopes and a fusion carries ONE op_name, so an inner scope's
          seconds are those of the fusions named after it. The line nests: a
          while op's event spans the events of its body;
  spans   every "pqt:" event of the host planes (name cut at '#', where the
          annotation's arguments start), whatever thread line it is on.
"""

from __future__ import annotations

import functools
from pathlib import Path

from xplane import OPS_LINE, WINDOW, clip, newest_xplane, union

TRACE_DIR = Path(__file__).resolve().parent.parent / ".cache" / "trace"
PREFIX = "pqt:"
SCOPE_MARK = "pqt."
# where the TPU runtime puts an op's op_name metadata: "tf_op" on the v5e
# (libtpu 0.0.34, PERF.md section 5); the first that holds a pqt. path wins
SCOPE_STATS = ("tf_op", "hlo_op", "name", "long_name")


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def fields(buf):
    """(field number, value) of one protobuf message: an int for a varint or
    a fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value = int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"xspans: protobuf wire type {kind} in an XSpace")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _metadata(plane) -> tuple:
    """({event metadata id: (name, {stat metadata id: value})}, {stat metadata
    id: name}) of one XPlane (fields 4 and 5: maps of key 1 -> message 2)."""
    events, stats = {}, {}
    for no, entry in plane:
        if no not in (4, 5):
            continue
        message = next((v for f, v in fields(entry) if f == 2), b"")
        ident, name, own = 0, "", {}
        for f, v in fields(message):
            if f == 1:
                ident = v
            elif f == 2:
                name = _text(v)
            elif f == 5 and no == 4:  # XEventMetadata.stats: XStat(metadata_id 1, str 5, ref 7)
                stat = dict(fields(v))
                own[stat.get(1, 0)] = stat
        if no == 4:
            events[ident] = (name, own)
        else:
            stats[ident] = name
    return events, stats


def _scope_path(own: dict, stat_names: dict) -> str:
    """The scope path among an op's metadata stats, or "" where none holds one."""
    found = {}
    for ident, stat in own.items():
        if 5 in stat:
            found[stat_names.get(ident, "")] = _text(stat[5])
        elif 7 in stat:  # a reference to a string interned as a stat's name
            found[stat_names.get(ident, "")] = stat_names.get(stat[7], "")
    for key in SCOPE_STATS:
        if SCOPE_MARK in found.get(key, ""):
            return found[key]
    return next((v for v in found.values() if SCOPE_MARK in v), "")


def _events(line):
    """(metadata id, start_ns, end_ns) of one XLine's events, on lib/xplane.py's
    arithmetic: start = int(timestamp_ns + offset_ps / 1000)."""
    stamp, raw = 0, []
    for no, v in line:
        if no == 3:
            stamp = v
        elif no == 4:
            raw.append(v)
    for ev in raw:
        ident = offset = duration = 0
        for f, v in fields(ev):
            if f == 1:
                ident = v
            elif f == 2:
                offset = v
            elif f == 3:
                duration = v
        start = stamp + offset / 1000.0
        yield ident, int(start), int(start + duration / 1000.0)


def extract(xspace: bytes) -> dict:
    """{"window": (lo, hi) or None, "ops": [(scope path, start_ns, end_ns)] of
    the first device, "spans": [(name, start_ns, end_ns)]} out of a
    serialized XSpace."""
    window = None
    devices: dict = {}
    spans: list = []
    for no, plane in fields(memoryview(xspace)):
        if no != 1:
            continue
        plane = list(fields(plane))
        name = next((_text(v) for f, v in plane if f == 2), "")
        meta, stat_names = _metadata(plane)
        lines = (list(fields(v)) for f, v in plane if f == 3)
        if name.startswith("/device:TPU:"):
            paths = {k: _scope_path(own, stat_names) for k, (_, own) in meta.items()}
            for line in lines:
                if any(f == 2 and _text(v) == OPS_LINE for f, v in line):
                    devices[name] = [(paths.get(k, ""), s, e) for k, s, e in _events(line)]
            continue
        mine = {k: n[len(PREFIX):].split("#", 1)[0] for k, (n, _) in meta.items() if n.startswith(PREFIX)}
        marks = {k for k, (n, _) in meta.items() if n == WINDOW}
        if not mine and not marks:
            continue
        for line in lines:
            for k, s, e in _events(line):
                if k in mine:
                    spans.append((mine[k], s, e))
                elif window is None and k in marks:
                    window = (s, e)
    first = min((name for name, ops in devices.items() if ops), default=None)
    return {"window": window, "ops": devices.get(first, []), "spans": spans}


@functools.lru_cache(maxsize=1)
def load(directory: Path = TRACE_DIR):
    """The newest trace under `directory`, extracted; None where there is none."""
    pb = newest_xplane(directory) if Path(directory).is_dir() else None
    return None if pb is None else extract(pb.read_bytes())


def scope_seconds(trace: dict, scope: str):
    """Seconds inside the window during which a device op ran whose scope path
    contains `scope` as whole path components: the union of their intervals,
    because the line nests (a while op's event spans its body's ops, and both
    may carry the scope). None where no op of the trace carries any scope (the
    program has none, or the runtime drops them)."""
    if trace["window"] is None or not any(path for path, _, _ in trace["ops"]):
        return None
    lo, hi = trace["window"]
    want = f"/{scope}/"
    hits = [(s, e) for path, s, e in trace["ops"] if want in f"/{path}/"]
    return sum(e - s for s, e in union(clip(hits, lo, hi))) / 1e9

