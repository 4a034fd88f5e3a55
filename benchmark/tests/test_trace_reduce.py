"""The trace reduction on a small synthetic trace with known answers."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402


def _xspace():
    """Two chips, a 100 us window.  Chip 0: a 40 us ``while`` holding a
    10 us fusion, then an all-reduce of 20 us after a 30 us gap.  Chip 1:
    one 50 us fusion.  The host runs ``feed`` across chip 0's gap."""
    us = 1000000  # picoseconds

    def ev(meta, start_us, dur_us):
        return "events { metadata_id: %d offset_ps: %d duration_ps: %d }" % (
            meta, start_us * us, dur_us * us)

    def meta(*names):
        return "\n".join(
            'event_metadata { key: %d value { id: %d name: "%s" } }'
            % (i + 1, i + 1, n) for i, n in enumerate(names))

    return """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000 %s %s %s }
  lines { name: "XLA Modules" timestamp_ns: 1000 %s }
  %s }
planes { name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 1000 %s }
  %s }
planes { name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 1000 %s %s }
  %s }
""" % (ev(1, 0, 40), ev(2, 10, 10), ev(3, 70, 20), ev(1, 0, 90),
       meta("%while.1", "%fusion.2", "%all-reduce.3"),
       ev(1, 20, 50), meta("%fusion.9"),
       ev(1, 0, 100), ev(2, 35, 40),
       meta(trace_reduce.WINDOW_ANNOTATION, "feed(batch)"))


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return trace_reduce.reduce_profile(ProfileData.from_text_proto(_xspace()))


def test_window_and_busy(reduced):
    assert reduced["chips"] == 2
    assert reduced["window_s"] == pytest.approx(100e-6)
    # chip 0 busy 60 us, chip 1 busy 50 us
    assert reduced["busy_s"] == pytest.approx(55e-6)


def test_self_time_and_collectives(reduced):
    ops = reduced["op_seconds"]
    # the while is not charged its body; sums are averaged over chips
    assert ops["%while.1"] == pytest.approx(15e-6)
    assert ops["%fusion.2"] == pytest.approx(5e-6)
    assert reduced["collective_s"] == pytest.approx(10e-6)
    assert reduced["device_ops"][0] == ["%fusion.9", pytest.approx(25e-6)]


def test_gaps_are_labelled_by_the_host_event_that_covers_them(reduced):
    gaps = dict(reduced["idle_gaps"])
    # chip 0: 40-70 us under feed(batch); its last 10 us and chip 1's two
    # gaps (0-20, 70-100) lie under the window annotation only
    assert gaps["feed"] == pytest.approx(15e-6)
    assert sum(gaps.values()) == pytest.approx(45e-6)
