"""Attribution of a traced window to the program's own names: idle gaps
by the frontend's host spans, device time by graph node, the clock tie,
and the per-layer metrics that read them."""
import dataclasses
import importlib.util
import types
from pathlib import Path

import pytest

from bench import attribution, run, trace
from bench.attribution import Op
from bench.tests.test_bench_trace import _events
from bench.trace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6
METRICS = Path(__file__).resolve().parents[1] / "metrics"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _frontend(*spans):
    return [Event(trace.HOST_PLANE, "spans", n, a * MS, b * MS)
            for n, a, b in spans]


# ---------------------------------------------------------------------------
# idle gaps

def test_a_gap_inside_poll_takes_the_batch_stage_covering_it():
    # the [5, 10] ms gap lies in bench.poll [0, 10]; the frontend packed
    # over [4, 9] of it while requests waited for their close
    events = _events() + _frontend(("frontend.close", 0, 9.5),
                                   ("frontend.pack", 4, 9),
                                   ("frontend.put", 9, 10))
    labels = [(name, s) for name, s, _ in attribution.label_gaps(events)]
    assert labels == [("bench.sleep", pytest.approx(0.035)),
                      ("bench.flush", pytest.approx(0.020)),
                      ("frontend.pack", pytest.approx(0.005))]
    cover = attribution.label_gaps(events)[2][2]
    assert cover == {"bench.poll": pytest.approx(1.0),
                     "frontend.close": pytest.approx(0.9),
                     "frontend.pack": pytest.approx(0.8),
                     "frontend.put": pytest.approx(0.2)}


def test_a_batch_stage_that_covers_little_of_a_gap_does_not_name_it():
    events = _events() + _frontend(("frontend.close", 5, 10),
                                   ("frontend.launch", 9.9, 10))
    assert attribution.label_gaps(events)[2][0] == "frontend.close"


@pytest.mark.parametrize("events", [
    _events(),
    [Event(DEV, "XLA Ops", "conv.1", 0, 5 * MS),
     Event(DEV, "XLA Ops", "conv.1", 20 * MS, 30 * MS),
     Event(HOST, "python", "bench.window", 0, 40 * MS),
     Event(HOST, "python", "bench.submit", 4 * MS, 8 * MS),
     Event(HOST, "python", "bench.poll", 8 * MS, 14 * MS),
     Event(HOST, "python", "bench.sleep", 14 * MS, 15 * MS),
     Event(HOST, "python", "bench.poll", 15 * MS, 18 * MS)],
], ids=["fixture", "poll_split_by_sleep"])
def test_the_loop_spans_alone_keep_the_labels_trace_gives(events):
    ours = [(name, s) for name, s, _ in attribution.label_gaps(events)]
    assert ours == trace.summarize(events).idle_gaps


def test_the_program_spans_leave_every_existing_metric_unchanged():
    """Adding the frontend's spans to the fixture's events changes no
    summary, so every per-layer metric the benchmark had reads the
    same."""
    spans = _frontend(("frontend.close", 0, 9), ("frontend.pack", 4, 9),
                      ("frontend.wait", 40, 60), ("frontend.fetch", 60, 61))
    before = trace.summarize(_events())
    after = trace.summarize(_events() + spans)
    assert after == before
    batch = types.SimpleNamespace(geometry="g", bucket=2, units=2,
                                  transfer_ms=7.0, transfer_t1=0.0)
    work = {("g", 2): [types.SimpleNamespace(
        flops=4e9, min_seconds=lambda f, b: 1e-3)]}
    peaks = run.load_peaks("TPU v5 lite")
    old = [m["name"] for m in run.load_benchmark()["per_layer"]
           if m["name"] not in ("pack_ms.bulk", "host_ms.interactive",
                                "close_ms.interactive", "glue_share.bulk")]
    for name in old:
        read = _reader(name).read
        wins = [run.Window(10.0, 0.0, 1.0, [], [batch], [], s, work, peaks,
                           10.0) for s in (before, after)]
        assert read(wins[0]) == read(wins[1]), name


# ---------------------------------------------------------------------------
# device time by graph node

def _ops():
    """Two programs of one bucket: conv1 (a pad, a dot that transforms
    its filter, and its Pallas kernel), then pool1 and the dense head
    (XLA's dot and its bias add), and one copy with no node scope."""
    def op(name, a, b, scope):
        # a TPU trace keeps the op_name as "<op_name>:<type>", type empty
        st = (("hlo_category", "loop fusion"),) + (
            (("tf_op", scope + ":"),) if scope else ())
        return Op(DEV, "XLA Ops", name, a * MS, b * MS, st)
    out = []
    for t in (0, 50):
        out += [
            Op(DEV, "XLA Modules", "jit_serve_b8(7)", t * MS, (t + 40) * MS),
            op("pad.3", t, t + 3, "jit(serve_b8)/conv1/jit(_pad)/pad"),
            op("fusion.7", t + 3, t + 5,
               "jit(serve_b8)/conv1/jit(<unknown>)/ij,jkcm->ilcm/dot_general"),
            op("cuconv_fused.1", t + 5, t + 20,
               "jit(serve_b8)/conv1/jit(<unknown>)/cuconv_fused/pallas_call"),
            op("reduce-window.2", t + 20, t + 25, "jit(serve_b8)/pool1/max"),
            op("fusion.9", t + 25, t + 30, "jit(serve_b8)/head/dot_general"),
            op("add.4", t + 30, t + 32, "jit(serve_b8)/head/add"),
            op("copy.5", t + 32, t + 40, None),
        ]
    return out


NODES = {"conv1", "pool1", "head"}
TIMES = {
    "conv1": {"kernel": pytest.approx(0.030), "glue": pytest.approx(0.010)},
    "pool1": {"kernel": 0.0, "glue": pytest.approx(0.010)},
    "head": {"kernel": pytest.approx(0.010), "glue": pytest.approx(0.004)},
    attribution.UNSCOPED: {"kernel": 0.0, "glue": pytest.approx(0.016)}}


def test_ops_split_into_per_node_kernel_and_glue_that_sum_to_busy():
    ops = _ops()
    times = attribution.node_times(ops, 0, 100 * MS, NODES)
    # conv1's filter-transform dot is glue: its kernel is the Pallas call
    assert times == TIMES
    events = ops + [Event(HOST, "python", "bench.window", 0, 100 * MS)]
    busy = trace.summarize(events).busy_s
    assert sum(sum(kg.values()) for kg in times.values()) == \
        pytest.approx(busy)
    assert attribution.top_nodes(times, 2) == [
        ("conv1", pytest.approx(0.030), pytest.approx(0.010)),
        ("head", pytest.approx(0.010), pytest.approx(0.004))]


@pytest.mark.parametrize("tf_op,node,kind", [
    ("jit(serve_b32)/fire7e1/jit(<unknown>)/jit(cuconv_fused)/cuconv_fused/"
     "pallas_call:", "fire7e1", "pallas"),
    ("jit(serve_b8)/conv1/jit(<unknown>)/conv_general_dilated:", "conv1",
     "xla"),
    ("jit(serve_b8)/head/dot_general:", "head", "xla"),
    ("jit(serve_b32)/s1b3c2/jit(<unknown>):", "s1b3c2", None),
    ("jit(serve_b2)/s3b1c3/jit(<unknown>)/jit(cuconv_fused)/"
     "transpose;reshape:", "s3b1c3", None),
    (None, None, None),
])
def test_the_trace_names_each_ops_node_and_kernel(tf_op, node, kind):
    """As a TPU trace gives them: the op_name in the metadata's tf_op."""
    op = Op(DEV, "XLA Ops", "%fusion.3 = f32[2] fusion(...)", 0, 1,
            (("tf_op", tf_op),) if tf_op else ())
    name = attribution.op_name(op)
    assert attribution.node_in(name, {"fire7e1", "conv1", "head", "s1b3c2",
                                      "s3b1c3", "serve_b8"}) == node
    assert attribution.mac_kind(name) == kind


def test_a_trace_without_node_scopes_charges_everything_unscoped():
    ops = [dataclasses.replace(o, stats=o.stats[:1]) for o in _ops()]
    times = attribution.node_times(ops, 10 * MS, 100 * MS, NODES)
    assert set(times) == {attribution.UNSCOPED}
    assert sum(times[attribution.UNSCOPED].values()) == pytest.approx(0.070)


def test_glue_share_reads_the_runs_own_profile(tmp_path, monkeypatch):
    from jax.profiler import ProfileData
    reader = _reader("glue_share.bulk")
    ops = _ops()
    stat_ids = {}

    def stat(name):
        return stat_ids.setdefault(name, len(stat_ids) + 1)
    lines = {}
    for i, o in enumerate(ops, 1):
        meta = " ".join(f'stats {{ metadata_id: {stat(k)} str_value: "{v}" }}'
                        for k, v in o.stats)
        lines.setdefault(o.line, []).append(
            (f"events {{ metadata_id: {i} offset_ps: {int(o.start_ns * 1e3)}"
             f" duration_ps: {int((o.end_ns - o.start_ns) * 1e3)} }}",
             f'event_metadata {{ key: {i} value {{ id: {i} name: "{o.name}" '
             f"{meta} }} }}"))
    text = "planes { id: 1 name: \"/device:TPU:0\" "
    for j, (line, rows) in enumerate(lines.items(), 1):
        text += (f'lines {{ id: {j} name: "{line}" timestamp_ns: 0 '
                 + " ".join(r[0] for r in rows) + " } ")
    text += " ".join(r[1] for rows in lines.values() for r in rows)
    text += " " + " ".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in stat_ids.items()) + " }"
    (tmp_path / "x.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(reader, "TRACE_DIR", tmp_path)
    loaded = attribution.load_device(str(tmp_path / "x.xplane.pb"))
    assert sorted(loaded, key=lambda o: o.start_ns) == \
        sorted(ops, key=lambda o: o.start_ns)

    # the window is [0, 100] ms, tied at the first program; busy 80 ms
    batch = types.SimpleNamespace(transfer_t1=5.0)
    work = {("g", 8): [types.SimpleNamespace(name="conv1"),
                       types.SimpleNamespace(name="head")]}
    summary = trace.Summary(0.1, 0.080, 1, [], [])
    win = run.Window(0.1, 5.0, 1.0, [], [batch], [], summary, work, {}, 5.1)
    # glue of conv1 (10 ms) and head (4 ms); pool1 is not a conv node
    assert reader.read(win) == pytest.approx(100 * 0.014 / 0.080)
    assert reader.read(dataclasses.replace(win, trace=None)) is None
    monkeypatch.setattr(reader, "TRACE_DIR", tmp_path / "none")
    assert reader.read(win) is None


# ---------------------------------------------------------------------------
# the clock tie

def test_a_program_that_starts_before_its_launch_is_flagged():
    window = [Event(HOST, "python", "bench.window", 0, 100 * MS)]
    mods = [Event(DEV, "XLA Modules", "jit_serve_b8(1)", t * MS, (t + 5) * MS)
            for t in (10, 30, 50)]
    ok = _frontend(("frontend.launch", 9.9, 10), ("frontend.launch", 29, 29.1),
                   ("frontend.launch", 45, 45.1))
    tie = attribution.launch_ties(window + mods + ok)
    assert tie["batches"] == tie["programs"] == 3
    # the first batch is the anchor: 1 ms and 5 ms after it
    assert tie["min_s"] == pytest.approx(0.001)
    assert tie["median_s"] == pytest.approx(0.003)
    assert tie["before_launch"] == 0
    late = _frontend(("frontend.launch", 9.9, 10),
                     ("frontend.launch", 31, 31.1),
                     ("frontend.launch", 45, 45.1))
    tie = attribution.launch_ties(window + mods + late)
    assert tie["before_launch"] == 1
    assert tie["min_s"] == pytest.approx(-0.001)


# ---------------------------------------------------------------------------
# the frontend span metrics

def _batch(pack, put, launch, wait, fetch, scatter):
    from repro.serve.telemetry import BatchTrace
    t = [0.0]
    for d in (pack, put, launch, wait, fetch, scatter):
        t.append(t[-1] + d / 1e3)
    b = BatchTrace(geometry="g", bucket=8, units=8, padded=0,
                   transfer_t0=t[1], transfer_t1=t[2], dispatch_t=t[3],
                   pack_t0=t[0])
    b.wait_t0, b.wait_t1, b.harvest_t, b.scatter_t1 = t[3], t[4], t[5], t[6]
    return b


def test_frontend_span_metrics_read_the_batch_and_request_stamps():
    from repro.serve.telemetry import RequestTrace
    batches = [_batch(2, 7, 0.1, 5, 0.2, 0.3), _batch(4, 7, 0.1, 5, 0.2, 0.3),
               _batch(6, 7, 0.1, 5, 0.2, 0.3)]
    requests = [RequestTrace(rid=i, geometry="g", images=1, status="served",
                             deadline_ms=None, queue_ms=2.0, transfer_ms=0.0,
                             compute_ms=0.0, total_ms=5.0, submit_t=0.0,
                             close_t=i / 1e3) for i in range(1, 21)]
    win = run.Window(1.0, 0.0, 1.0, [], batches, requests, None, {}, {}, 1.0)
    assert _reader("pack_ms.bulk").read(win) == pytest.approx(4.0)
    assert _reader("host_ms.interactive").read(win) == pytest.approx(11.6)
    assert _reader("close_ms.interactive").read(win) == pytest.approx(19.05)


def test_frontend_span_metrics_read_nothing_from_a_program_without_them():
    """A program that stamps no frontend spans (the benchmark's traces
    from before them) reads None, and nothing raises."""
    batch = types.SimpleNamespace(geometry="g", bucket=8, units=8,
                                  transfer_t0=0.0, transfer_t1=0.1)
    req = types.SimpleNamespace(status="served", queue_ms=1.0)
    win = run.Window(1.0, 0.0, 1.0, [], [batch], [req], None, {}, {}, 1.0)
    for name in ("pack_ms.bulk", "host_ms.interactive",
                 "close_ms.interactive", "glue_share.bulk"):
        assert _reader(name).read(win) is None, name
