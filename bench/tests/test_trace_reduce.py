"""The trace reduction on a small recorded trace: two TPU ops lines'
worth of events, named by their HLO instruction text as a TPU trace
names them, and the harness's host spans."""
import json
from pathlib import Path

from bench import counts, trace_reduce

DATA = Path(__file__).parent / "data" / "trace_small.json"
SPANS = ["bench.step_serve", "bench.submit", "bench.wait_arrival"]


def test_busy_idle_and_kernels():
    planes = json.loads(DATA.read_text())
    red = trace_reduce.reduce(planes, SPANS)
    assert abs(red["busy_s"] - 7e-6) < 1e-15      # union, overlap once
    assert red["op_calls"]["_collision_paged_pallas.1"] == 2
    s, c = trace_reduce.kernel_time(red, ("_collision_paged_pallas",))
    assert trace_reduce.kernel_time(red, ("_collision",)) == (0.0, 0)
    assert c == 2 and abs(s - 3e-6) < 1e-15
    assert red["breakdown"]["device_ops"][0][0].startswith(
        "fusion.1 = f32[8]{0} fusion(")


def test_nested_ops_count_their_own_time():
    """A ``while`` holds its body's ops on the same line: each op is
    charged its own time, and the busy time is the loop's span."""
    ops = [("%while.7 = (s32[]) while(%t), condition=%c, body=%b", 0, 10000),
           ("%fusion.4 = f32[8]{0} fusion(%x), kind=kLoop", 1000, 2000),
           ("%_rerank_pallas.2 = f32[12,1,512]{2,1,0} custom-call(%y)",
            4000, 5000),
           ("%fusion.5 = f32[8]{0} fusion(%z), kind=kLoop", 12000, 1000)]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [list(e) for e in ops]}]}]
    red = trace_reduce.reduce(planes, SPANS)
    assert abs(red["busy_s"] - 11e-6) < 1e-15
    assert abs(red["op_s"]["while.7"] - 3e-6) < 1e-15
    assert trace_reduce.kernel_time(red, ("_rerank_pallas",))[1] == 1
    assert abs(sum(red["op_s"].values()) - 11e-6) < 1e-15
    assert red["breakdown"]["device_ops"][0] == [ops[2][0][1:], 5e-6]


def test_gap_labels():
    planes = json.loads(DATA.read_text())
    red = trace_reduce.reduce(planes, SPANS)
    labels = dict((round(g * 1e9), n) for n, g in red["gaps"])
    assert labels == {3000: "bench.wait_arrival",
                      1000: "bench.submit",
                      2000: trace_reduce.NO_SPAN}
    assert red["breakdown"]["idle_gaps"][0] == ["bench.wait_arrival", 3e-6]


def test_no_device_plane_reads_nothing():
    red = trace_reduce.reduce([{"name": "/host:CPU", "lines": []}], SPANS)
    assert red["busy_s"] == 0.0

    class R:
        trace = red
        tt0, tt1 = 0.0, 1.0
    assert counts.idle_share(R) is None
