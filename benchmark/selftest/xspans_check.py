"""lib/xspans.py and the scope reader over it, checked on a hand-built trace
whose numbers are known.

    python benchmark/selftest/xspans_check.py

fixture.xspans.txt is an XSpace in text form (one TPU plane with scoped ops,
one host plane with "pqt:" annotations on three thread lines); its header
says what it holds and works every number out by hand. Exits non-zero on the
first number that is off.
"""

import sys
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "lib"), str(HERE.parent / "readers")]

import xspans  # noqa: E402


def main() -> int:
    from jax.profiler import ProfileData

    text = (HERE / "fixture.xspans.txt").read_text()
    trace = xspans.extract(ProfileData.text_proto_to_serialized_xspace(text))
    assert trace["window"] == (1000, 21000), trace["window"]
    assert len(trace["ops"]) == 8 and sum(1 for path, _, _ in trace["ops"] if path) == 7
    names = sorted({name for name, _, _ in trace["spans"]})
    assert names == ["chunk.prepare", "deliver", "dispatch", "dispatch.launch", "dispatch.upload", "io.read"], names

    ns = lambda scope: round(xspans.scope_seconds(trace, scope) * 1e9)  # noqa: E731
    assert ns("pqt.hybrid_expand") == 5000
    assert ns("pqt.hybrid_expand/find_run") == 4000  # the while's 3000 hold its body's 2600
    assert ns("pqt.delta_decode") == 4500  # 3000 + 1000 + the 500 the window leaves of the last op
    assert ns("pqt.delta_decode/prefix_sum") == 1000
    assert ns("pqt.prefix_sum") == 1000  # a kernel inlined in another keeps its own scope
    assert ns("pqt.hybrid") == 0  # whole path components only
    assert ns("pqt.dict_gather") == 0  # scopes are there, this one ran nothing

    # a program without scopes or annotations (the parent of PR 26): nothing to read, nothing raised
    bare = dict(trace, ops=[("", s, e) for _, s, e in trace["ops"]], spans=[])
    assert xspans.scope_seconds(bare, "pqt.hybrid_expand") is None

    # the reader: None on a rehearsal (obs.xplane is None), numbers over the denominator otherwise
    import xplane_scope

    xplane_scope.load = lambda: trace
    obs = SimpleNamespace(xplane={"busy_s": 10000 / 1e9}, rows=2_000_000, window_s=20000 / 1e9)
    assert xplane_scope.read(SimpleNamespace(xplane=None), "pqt.hybrid_expand", "mrow") is None
    ms_per_mrow = lambda ns_: ns_ / 1e9 * 1e3 / 2.0  # noqa: E731
    assert abs(xplane_scope.read(obs, "pqt.hybrid_expand/find_run", "mrow") - ms_per_mrow(4000)) < 1e-12
    print("xspans_check: ok (scopes 5000/4000/4500 ns of a 20000 ns window)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
