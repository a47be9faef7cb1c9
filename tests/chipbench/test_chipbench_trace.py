"""The reduction from a profiler trace to busy time, idle gaps, top ops and
per-program time (``chipbench.trace``), on traces whose numbers are worked
out by hand."""
from __future__ import annotations

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"

# One device, times in ns.  Ops: fusion.1 [1000, 3000], convolution.2
# [2000, 5000], fusion.1 [8000, 9000]; the program jit_step(1) spans
# [1000, 5000] and jit_step(2) [8000, 9000].  Host spans: window
# [500, 9500], dispatch [600, 1200], sync [1200, 5200], data [5200, 8000].
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "convolution.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(1)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step(2)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 600000 }
    events { metadata_id: 3 offset_ps: 1200000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 5200000 duration_ps: 2800000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "sync" } }
  event_metadata { key: 4 value { id: 4 name: "data" } } }
"""


def test_synthetic_trace_by_hand():
    ev = trace.read_events(ProfileData.from_text_proto(SYNTHETIC))
    red = trace.reduce_events(ev, ["dispatch", "sync", "data"])
    # busy: [1000, 5000] and [8000, 9000] -> 4000 + 1000 ns
    assert red["busy_s"] == pytest.approx(5000e-9)
    assert red["window_s"] == pytest.approx(9000e-9)
    # own time: convolution.2 3000 ns; fusion.1 2000 + 1000 ns less the
    # 1000 ns in which convolution.2, begun inside it, also ran
    assert red["device_ops"] == [["convolution.2", pytest.approx(3000e-9)],
                                 ["fusion.1", pytest.approx(2000e-9)]]
    assert red["module_s"] == {"jit_step": pytest.approx(5000e-9)}
    # gaps: [5000, 8000] (middle 6500: data), [500, 1000] (middle 750:
    # dispatch), [9000, 9500] (middle 9250: no span)
    assert red["idle_gaps"] == [["data", pytest.approx(3000e-9)],
                                ["dispatch", pytest.approx(500e-9)],
                                ["other", pytest.approx(500e-9)]]


def test_no_window_or_no_device_reads_nothing():
    ev = trace.read_events(ProfileData.from_text_proto(SYNTHETIC))
    assert trace.reduce_events({"devices": ev["devices"], "spans": []},
                               ["sync"]) is None
    assert trace.reduce_events({"devices": {}, "spans": ev["spans"]},
                               ["sync"]) is None


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == \
        [[1, 4.5], [5, 6]]


def test_recorded_chip_trace_by_hand():
    """``data/small.xplane.pb``: three calls of one program on a TPU v5e
    (``record_trace.py``).  Each call runs copy-start, copy-done,
    convolution_tanh_fusion and fusion; the ops' intervals, in ns:
    call 1: [47945308, 47945321] [47945322, 47945324] [47945326, 48035279]
    [48035281, 48126218]; call 2: [51207211, 51207224] [51207225, 51207228]
    [51207229, 51297182] [51297184, 51388055]; call 3: [54483423, 54483436]
    [54483438, 54483440] [54483442, 54573395] [54573396, 54664289].
    Window [45307810, 55537280]."""
    red = trace.reduce_file(str(DATA / "small.xplane.pb"),
                            ["data", "dispatch", "sync"])
    call1 = 13 + 2 + 89953 + 90937
    call2 = 13 + 3 + 89953 + 90871
    call3 = 13 + 2 + 89953 + 90893
    assert red["busy_s"] == pytest.approx((call1 + call2 + call3) * 1e-9)
    assert red["window_s"] == pytest.approx((55537280 - 45307810) * 1e-9)
    ops = dict(red["device_ops"])
    assert ops["fusion bf16[2048,2048]"] == pytest.approx(
        (90937 + 90871 + 90893) * 1e-9)
    assert ops["convolution_tanh_fusion bf16[2048,2048]"] == \
        pytest.approx(3 * 89953e-9)
    assert red["module_s"] == {"jit__lambda": pytest.approx(
        (180918 + 180852 + 180873) * 1e-9)}
    # the longest gaps: between calls 2 and 3 (middle in the second
    # sleep's data span), between calls 1 and 2, before call 1, after call 3
    want = [("data", 54483423 - 51388055), ("data", 51207211 - 48126218),
            ("data", 47945308 - 45307810), ("sync", 55537280 - 54664289)]
    for (name, t), (wn, wt) in zip(red["idle_gaps"], want):
        assert name == wn and t == pytest.approx(wt * 1e-9)


def test_nested_ops_count_their_own_time():
    own = trace.self_times([(0, 100, "while"), (10, 30, "a"), (40, 90, "b"),
                            (50, 60, "c")])
    assert own == {"while": 30, "a": 20, "b": 40, "c": 10}
