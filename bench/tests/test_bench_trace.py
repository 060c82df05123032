"""The reduction from profiler events to busy time, top operations and
idle gaps."""
import pytest

from bench import trace
from bench.trace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _events():
    ms = 1e6
    return [
        Event(HOST, "python", "bench.window", 0, 100 * ms),
        Event(HOST, "python", "bench.poll", 0, 10 * ms),
        Event(HOST, "python", "bench.flush", 40 * ms, 70 * ms),
        Event(HOST, "python", "bench.sleep", 70 * ms, 100 * ms),
        # ops: overlap on the line counts once; the one before the
        # window is clipped
        Event(DEV, "XLA Ops", "conv.1", -5 * ms, 5 * ms),
        Event(DEV, "XLA Ops", "conv.1", 10 * ms, 30 * ms),
        Event(DEV, "XLA Ops", "fusion.2", 20 * ms, 40 * ms),
        Event(DEV, "XLA Ops", "conv.1", 60 * ms, 65 * ms),
        # not an op line, not a device plane
        Event(DEV, "XLA Modules", "jit_fn", 0, 100 * ms),
        Event(HOST, "XLA Ops", "host.thing", 0, 100 * ms),
    ]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    s = trace.summarize(_events())
    assert s.window_s == pytest.approx(0.1)
    # [0,5] + [10,40] + [60,65] = 40 ms
    assert s.busy_s == pytest.approx(0.040)
    assert s.chips == 1


def test_top_ops_sum_clipped_own_time_by_kind():
    s = trace.summarize(_events())
    # conv: [0,5] + [10,20] (fusion.2 starts inside it) + [60,65]
    assert dict(s.device_ops) == {"conv": pytest.approx(0.020),
                                  "fusion": pytest.approx(0.020)}


def test_idle_gaps_are_named_by_the_host_span_covering_them():
    s = trace.summarize(_events())
    # gaps: [65,100] sleep 35ms, [40,60] flush 20ms, [5,10] poll 5ms
    assert s.idle_gaps == [("bench.sleep", pytest.approx(0.035)),
                           ("bench.flush", pytest.approx(0.020)),
                           ("bench.poll", pytest.approx(0.005))]


def test_a_trace_without_its_window_or_device_ops_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize([e for e in _events() if e.name != "bench.window"])
    with pytest.raises(ValueError, match="no device operation"):
        trace.summarize([e for e in _events() if e.plane != DEV])


def test_host_spans_are_tied_to_the_trace_at_the_first_program():
    ms = 1e6
    dev = [Event(DEV, "XLA Modules", "jit_fn", 1000 * ms, 1030 * ms),
           Event(DEV, "XLA Ops", "conv.1", 1000 * ms, 1030 * ms),
           Event(DEV, "XLA Ops", "conv.1", 1060 * ms, 1070 * ms)]
    # on the host's clock the window opens at 5.000 s and the first
    # batch is on the device at 5.010 s: the window is [990, 1090] ms
    # on the trace's clock, the flush [1025, 1045] ms
    host = trace.host_events(dev, [("bench.window", 5.0, 5.1),
                                   ("bench.flush", 5.035, 5.055)], 5.010)
    assert {e.plane for e in host} == {trace.HOST_PLANE}
    s = trace.summarize(dev + host)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.040)
    assert s.idle_gaps == [("bench.flush", pytest.approx(0.030)),
                           ("other", pytest.approx(0.020)),
                           ("other", pytest.approx(0.010))]
    with pytest.raises(ValueError, match="no device program"):
        trace.host_events([], [("bench.window", 5.0, 5.1)], 5.010)


def test_union_merges_touching_and_nested_intervals():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4), (7, 9), (7.5, 8)]) \
        == [(0, 4), (5, 6), (7, 9)]


def test_nested_ops_count_their_own_time_under_their_kind():
    ms = 1e6
    events = [
        Event(HOST, "python", "bench.window", 0, 100 * ms),
        Event(DEV, "XLA Ops", "%while.8 = (s32[]) while(...)", 0, 50 * ms),
        Event(DEV, "XLA Ops", "%dyn_fusion.3 = f32[2] fusion(...)",
              10 * ms, 20 * ms),
        Event(DEV, "XLA Ops", "%dyn_fusion.4 = f32[2] fusion(...)",
              30 * ms, 45 * ms),
        Event(DEV, "XLA Ops", "%broadcast.186.clone = f32[2] broadcast()",
              60 * ms, 70 * ms),
    ]
    s = trace.summarize(events)
    assert s.busy_s == pytest.approx(0.060)
    assert dict(s.device_ops) == {"dyn_fusion": pytest.approx(0.025),
                                  "while": pytest.approx(0.025),
                                  "broadcast": pytest.approx(0.010)}
    own = trace.self_times([e for e in events if e.plane == DEV])
    assert sum(b - a for _, a, b in own) == pytest.approx(60 * ms)


@pytest.mark.parametrize("name,kind", [
    ("%cuconv_fused.54 = f32[8,32] custom-call(...)", "cuconv_fused"),
    ("%reduce_window.0.clone = f32[8] reduce-window(...)", "reduce_window"),
    ("%copy = f32[2] copy(...)", "copy"),
    ("plain", "plain"),
])
def test_op_kind_drops_the_instruction_number(name, kind):
    assert trace.op_kind(name) == kind
