"""The trace reduction, checked on a hand-built trace whose numbers are known.

    python benchmark/selftest/xplane_check.py

fixture.xspace.txt is an XSpace in text form (one TPU plane, one host plane);
its header says what it holds. Exits non-zero on the first number that is off.
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "lib"))

from xplane import extract, reduce_intervals, union  # noqa: E402


def main() -> int:
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto((HERE / "fixture.xspace.txt").read_text())
    devices, window = extract(data)
    assert window == (1000, 11000), window
    spans = [("whole run", 0, 20000), ("read file", 900, 5500),
             ("wait block_until_ready", 5500, 9000), ("verify", 9000, 12000)]
    got = reduce_intervals(devices, window, spans)
    want = {
        "window_s": 10000 / 1e9, "busy_s": 4500 / 1e9, "devices": 1, "events": 4,
        "device_ops": [["jit_delta_packed_decode_device", 4500 / 1e9], ["jit_digest", 1000 / 1e9]],
        "idle_gaps": [["verify", 2500 / 1e9], ["wait block_until_ready", 2000 / 1e9], ["read file", 1000 / 1e9]],
    }
    assert got == want, f"\n got {got}\nwant {want}"
    assert union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    # no span open: the gap is still counted, under its own label
    bare = reduce_intervals(devices, window, [])
    assert bare["idle_gaps"] == [["no benchmark span open", 5500 / 1e9]], bare["idle_gaps"]
    # a window in which nothing ran: busy 0, which run.py refuses on a chip
    assert reduce_intervals(devices, (20000, 30000), [])["busy_s"] == 0.0
    print("xplane_check: ok (busy 4500 ns of a 10000 ns window; top ops and gaps as built)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
