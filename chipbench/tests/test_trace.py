"""The reduction from a trace to busy time, idle share, exposed
collectives, the costliest operations and the labelled idle gaps: on a
hand-made trace whose answers are counted by hand, and on small traces
recorded on a v5e (one chip, and four)."""
import json
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"

TPU_NAMES = [
    ("%while.2 = (f32[]{:T(128)}, pred[]{:T(512)}) while((f32[]{:T(128)}, "
     "pred[]{:T(512)}) %tuple.127), condition=%c, body=%b",
     ("while.2", "while")),
    ("%multiply_add_fusion.12 = f32[128,128,128]{2,1,0:T(8,128)S(1)} "
     "fusion(f32[128,128,127]{2,1,0:T(8,128)S(1)} %g.930), kind=kLoop",
     ("multiply_add_fusion.12", "fusion")),
    ("%slice-start.28 = ((f32[2097152]{0:T(1024)}), f32[524288]{0}, "
     "s32[]{:S(2)}) async-start(f32[2097152]{0:T(1024)} %b.1)",
     ("slice-start.28", "async-start")),
    ("%all-reduce.3 = f32[9]{0} all-reduce(f32[9]{0} %p), "
     "replica_groups={{0,1,2,3}}", ("all-reduce.3", "all-reduce")),
    ("bench.solve", ("bench.solve", "")),
]


@pytest.mark.parametrize("text,parts", TPU_NAMES)
def test_op_parts(text, parts):
    assert trace.op_parts(text) == parts


def hand_trace():
    """Window [100, 200) ns; device 0 runs fusions at [100, 130) and
    [140, 170) inside a while loop spanning [100, 170), and an all-reduce
    at [160, 180) that compute hides for 10 ns; device 1 is busy
    [100, 150); device 2 belongs to another cell."""
    return {
        "devices": {
            "/device:TPU:0": [
                ["while.1", "while", 100.0, 70.0],
                ["fusion.1", "fusion", 100.0, 30.0],
                ["fusion.2", "fusion", 140.0, 30.0],
                ["all-reduce.1", "all-reduce", 160.0, 20.0],
            ],
            "/device:TPU:1": [["fusion.1", "fusion", 90.0, 60.0]],
            "/device:TPU:2": [["fusion.9", "fusion", 100.0, 100.0]],
        },
        "host": [["bench.window", 100.0, 100.0],
                 ["bench.solve", 95.0, 40.0],
                 ["bench.verify", 175.0, 30.0]],
    }


def test_hand_trace():
    s = trace.summarize(hand_trace(), [0, 1])
    assert s.devices == 2
    assert s.window_s == pytest.approx(100e-9)
    # device 0: [100,130) + [140,180) = 70 ns; device 1: [100,150) = 50
    assert s.busy_s == pytest.approx(60e-9)
    assert s.idle_frac == pytest.approx(0.4)
    # all-reduce [160,180), compute until 170: 10 ns exposed on device 0
    assert s.collective_exposed_s == pytest.approx(5e-9)
    assert dict(s.top_ops)["fusion.1"] == pytest.approx((30 + 50) / 2e9)
    assert "while.1" not in dict(s.top_ops)
    # gaps: device 1's [150,200) and device 0's [180,200) have their
    # middles in bench.verify, device 0's [130,140) in bench.solve
    assert s.idle_gaps[0] == ("bench.verify", pytest.approx(50e-9))
    assert ("bench.solve", pytest.approx(10e-9)) in s.idle_gaps


def test_nothing_on_the_cell_devices():
    assert trace.summarize(hand_trace(), [5]) is None


def test_one_window_span():
    t = hand_trace()
    t["host"].append(["bench.window", 300.0, 10.0])
    with pytest.raises(ValueError):
        trace.summarize(t, [0])


@pytest.mark.parametrize("name,chips", [("tpu_trace_1chip.json", 1),
                                        ("tpu_trace_4chip.json", 4)])
def test_recorded_tpu_trace(name, chips):
    """Two solves and their checks, recorded on v5e chips: a device op
    stream as the TPU reports it, reduced to numbers inside their
    bounds."""
    t = json.loads((DATA / name).read_text())
    s = trace.summarize(t, range(chips))
    assert s.devices == chips
    assert 0 < s.busy_s < s.window_s
    assert 0 <= s.collective_exposed_s <= s.busy_s
    if chips == 1:
        # the numbers this reduction gave when the trace was recorded
        assert s.collective_exposed_s == 0
        assert s.window_s == pytest.approx(5.345421e-3)
        assert s.busy_s == pytest.approx(4.266e-5)
        assert s.top_ops[0] == ("fusion.9", pytest.approx(6.175e-6))
        assert s.idle_gaps[0][0] == "bench.solve"
    else:
        assert s.busy_s == pytest.approx(1.95692e-4)
        assert s.collective_exposed_s == pytest.approx(1.0265075e-4)
        assert s.top_ops[0] == ("psum.21", pytest.approx(2.191675e-5))
    assert s.top_ops and all(v > 0 for _, v in s.top_ops)
    assert {g for g, _ in s.idle_gaps} <= {
        "bench.window", "bench.solve", "bench.verify"}
    assert len(s.top_ops) <= trace.TOP and len(s.idle_gaps) <= trace.TOP
