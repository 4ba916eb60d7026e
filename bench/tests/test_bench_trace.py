"""The trace reduction, on a small trace recorded on one TPU v5e chip
(``bench/fixtures/small.xplane.pb``: inside a ``bench.window``
annotation, the n-body kernel at n = 2048, a 50 ms ``bench.idle`` sleep,
the neighbor kernel and the n-body kernel again), checked against plain
recomputations from the trace's events."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import profile              # noqa: E402

FIXTURE = ROOT / "bench" / "fixtures" / "small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return profile.read(str(FIXTURE))


@pytest.fixture(scope="module")
def events():
    """(device op events in the window, the window, host annotations)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(FIXTURE))
    ops, ann = [], {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name.startswith("/device:TPU:") and \
                        line.name == "XLA Ops":
                    ops.append((ev.start_ns, ev.end_ns, ev.name))
                elif ev.name.startswith("bench."):
                    ann[ev.name] = (ev.start_ns, ev.end_ns)
    return ops, ann


def test_fixture_has_one_chip_and_the_window(trace, events):
    ops, ann = events
    assert trace["device_count"] == 1
    assert trace["window_ns"] == tuple(int(x) for x in ann["bench.window"])
    assert trace["window_s"] == pytest.approx(
        (ann["bench.window"][1] - ann["bench.window"][0]) / 1e9)
    assert ops


def test_busy_is_the_union_of_op_intervals(trace, events):
    ops, ann = events
    lo, hi = ann["bench.window"]
    # plain sweep over the clipped intervals, in time order
    busy, reach = 0, lo
    for s, e, _ in sorted((max(s, lo), min(e, hi), n) for s, e, n in ops
                          if e > lo and s < hi):
        if e > reach:
            busy += e - max(s, reach)
            reach = e
    assert trace["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < trace["busy_s"] < trace["window_s"]


def test_idle_holds_the_sleep_and_is_named_by_the_host(trace, events):
    _, ann = events
    idle = trace["idle"]
    longest = idle[np.argmax(idle[:, 1] - idle[:, 0])]
    assert (longest[1] - longest[0]) / 1e9 >= 0.05
    named = profile.name_gaps(idle, trace["host"])
    assert named[0][0] == "bench.idle"
    total_idle = float((idle[:, 1] - idle[:, 0]).sum()) / 1e9
    assert sum(v for _, v in named) == pytest.approx(total_idle)
    assert total_idle == pytest.approx(trace["window_s"] - trace["busy_s"])


#: a chip that filled its trace buffers: two operations, then a stretch
#: of dropped events that overlaps the second; the window is 12 us long
DROPPED = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA TraceMe" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "%while.2 = f32[8] while()" } }
  event_metadata { key: 3 value { id: 3 name: "Trace Buffers Dropped" } }
  stats { metadata_id: 9 int64_value: 1234 }
  stat_metadata { key: 9 value { id: 9 name: "dropped_traces" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


@pytest.fixture(scope="module")
def dropped():
    from jax.profiler import ProfileData
    return profile.reduce(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(DROPPED)))


def test_a_dropped_stretch_counts_busy(dropped):
    # operations over [1, 3) and [4, 6) us, dropped over [5, 10) us
    assert dropped["window_s"] == pytest.approx(12e-6)
    assert dropped["busy_s"] == pytest.approx(8e-6)
    assert dropped["idle"].tolist() == [[0, 1000], [3000, 4000],
                                        [10000, 12000]]


def test_a_dropped_stretch_is_reported_and_no_operation_grows(dropped):
    assert dropped["dropped_s"] == pytest.approx(5e-6)
    assert dropped["dropped_events"] == 1234
    assert dropped["ops"] == pytest.approx({"fusion.1": 2e-6,
                                            "while.2": 2e-6})


def test_the_fixture_dropped_nothing(trace):
    assert trace["dropped_s"] == 0 and trace["dropped_events"] == 0


def test_union_and_gaps_on_overlapping_intervals():
    iv = np.array([[0, 10], [5, 20], [30, 40], [35, 36], [50, 60]])
    assert profile.union_seconds(iv) == pytest.approx(40e-9)
    assert profile.gaps(iv, 0, 70).tolist() == [[20, 30], [40, 50], [60, 70]]
    assert profile.gaps(np.zeros((0, 2), np.int64), 3, 9).tolist() == [[3, 9]]


def test_peak_table_knows_the_chip_and_refuses_others():
    from bench.lib import peaks
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
