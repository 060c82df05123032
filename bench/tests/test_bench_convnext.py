"""The ConvNeXt-T configuration: published work and parameters per
image, stage shapes, the program against the plain reference at a tiny
size, and the per-layer readers of its cell."""
import dataclasses
import importlib.util
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import attribution, run, trace, work
from bench.attribution import Op
from bench.configs import convnext_tiny
from repro.core.graph import GraphBuilder
from repro.models.cnn import GraphModel

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "convnext_tiny.json").read_text())
TINY = dict(image_size=32, stage_widths=[8, 16, 16, 24],
            stage_depths=[1, 2, 1, 1], num_classes=10)
DEV, MS = "/device:TPU:0", 1e6


def _graph(cfg=CFG, batch=1):
    b = GraphBuilder((batch, cfg["image_size"], cfg["image_size"],
                      cfg["in_channels"]))
    convnext_tiny.build(b, cfg)
    return b.graph()


def test_work_and_parameters_per_image_match_the_published_counts():
    g = _graph()
    macs = sum(n.macs for n in work.graph_work(g))
    assert macs == pytest.approx(CFG["macs_per_image_published"], rel=0.01)
    params = jax.eval_shape(lambda k: convnext_tiny.init(k, CFG),
                            jax.random.key(0))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    assert n_params == pytest.approx(CFG["params_published"], rel=0.01)
    # every conv, dense and norm node has its params, and nothing else
    assert set(params) == {n.name for n in g.nodes
                           if n.op in ("conv", "dense", "norm")}


def test_stage_shapes_and_node_counts():
    g = _graph()
    assert [g.shapes[f"s{s}b1add"][1:] for s in (1, 2, 3, 4)] == [
        (56, 56, 96), (28, 28, 192), (14, 14, 384), (7, 7, 768)]
    assert g.out_shape == (1, 1000)
    dw, norms = convnext_tiny.dw_nodes(CFG), convnext_tiny.norm_nodes(CFG)
    assert (len(dw), len(norms)) == (18, 23)
    assert [n.name for n in g.nodes if n.op == "norm"] == sorted(
        norms, key=[n.name for n in g.nodes].index)
    assert {n.name for n in g.conv_nodes if n.spec.groups > 1} == set(dw)
    gelu = [n for n in g.conv_nodes if n.spec.epilogue == "bias_gelu"]
    assert len(gelu) == 18
    # the stage-1 GELU tensor of a 32-image batch: 154 MB of float32
    assert 32 * np.prod(g.shapes["s1b1pw1"][1:]) * 4 == 154_140_672


def test_program_graph_matches_the_plain_reference():
    """The graph, planned and fused by the program, against the lax
    reference, at a tiny size on the CPU."""
    cfg = dict(CFG, **TINY)

    def builder(in_shape, policy):
        b = GraphBuilder(in_shape, policy)
        convnext_tiny.build(b, cfg)
        return b.graph()

    model = GraphModel(builder, (32, 32, 3))
    params = jax.jit(lambda k: convnext_tiny.init(k, cfg))(jax.random.key(5))
    x = np.random.default_rng(5).standard_normal((3, 32, 32, 3),
                                                 dtype=np.float32)
    got = np.asarray(model.apply(params, x))
    ref = np.asarray(convnext_tiny.reference(params, x, cfg))
    assert got.shape == (3, 10)
    np.testing.assert_allclose(got, ref, rtol=1e-4,
                               atol=1e-4 * np.abs(ref).max())
    assert model.graph_plan(x.shape).fused == {
        f"{name}pw2": f"add:{name}add"
        for name, _, _ in convnext_tiny.blocks(cfg)}


# ---------------------------------------------------------------------------
# the readers

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name.replace('.', '_')}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ops():
    """One program of bucket 8 on a 100 ms window: s1b1dw's kernel
    (8 ms) and a pad (2 ms), s1b1norm (5 ms), a 1x1 conv (20 ms), the
    head's norm (1 ms) and an unscoped copy (4 ms); busy 40 ms."""
    def op(name, a, b, scope):
        return Op(DEV, "XLA Ops", name, a * MS, b * MS,
                  (("tf_op", scope + ":"),) if scope else ())
    return [
        Op(DEV, "XLA Modules", "jit_serve_b8(1)", 0, 40 * MS),
        op("pad.1", 0, 2, "jit(serve_b8)/s1b1dw/jit(_pad)/pad"),
        op("depthwise_tap.2", 2, 10, "jit(serve_b8)/s1b1dw/jit(<unknown>)/"
           "jit(depthwise_tap)/depthwise_tap/pallas_call"),
        op("fusion.3", 10, 15, "jit(serve_b8)/s1b1norm/reduce_sum"),
        op("cuconv_fused.4", 15, 35, "jit(serve_b8)/s1b1pw1/jit(<unknown>)/"
           "cuconv_fused/pallas_call"),
        op("fusion.5", 35, 36, "jit(serve_b8)/headnorm/rsqrt"),
        op("copy.6", 36, 40, None),
    ]


def _window(monkeypatch, ops):
    monkeypatch.setattr(trace, "find_xplane", lambda d: "x.xplane.pb")
    monkeypatch.setattr(attribution, "load_device", lambda path: ops)
    nodes = [n for n in work.graph_work(_graph(batch=8))
             if n.name in ("s1b1dw", "s1b1pw1", "head")]
    batch = types.SimpleNamespace(geometry="224x224x3", bucket=8, units=8,
                                  transfer_t1=5.0)
    summary = trace.Summary(0.1, 0.040, 1, [], [])
    peaks = run.load_peaks("TPU v5 lite")
    return run.Window(0.1, 5.0, 1.0, [], [batch], [], summary,
                      {("224x224x3", 8): nodes}, peaks, 5.1)


def test_readers_read_nothing_without_a_trace(monkeypatch):
    win = _window(monkeypatch, _ops())
    no_trace = dataclasses.replace(win, trace=None)
    for name in ("dw_roofline.bulk", "norm_share.bulk", "mfu.convnext_bulk"):
        assert _reader(name).read(no_trace) is None, name
    # a trace whose ops carry no node scopes: nothing to charge
    bare = [Op(o.plane, o.line, o.name, o.start_ns, o.end_ns) for o in
            _ops()]
    win = _window(monkeypatch, bare)
    assert _reader("dw_roofline.bulk").read(win) is None
    assert _reader("norm_share.bulk").read(win) is None


def test_readers_charge_the_nodes_of_a_hand_built_window(monkeypatch):
    win = _window(monkeypatch, _ops())
    peaks = win.peaks
    dw = {n.name: n for n in win.work["224x224x3", 8]}["s1b1dw"]
    # the depthwise node's roofline bound over its kernel's 8 ms (the
    # pad is glue): 8 images of 56x56x96 in and out, the taps, the bias
    assert dw.bytes == 4 * (2 * 8 * 56 * 56 * 96 + 49 * 96 + 96)
    bound = dw.bytes / peaks["hbm_bytes_per_s"]
    assert _reader("dw_roofline.bulk").read(win) == pytest.approx(
        100 * bound / 0.008)
    # s1b1norm 5 ms and headnorm 1 ms of 40 ms busy
    assert _reader("norm_share.bulk").read(win) == pytest.approx(15.0)
    flops = sum(n.flops for n in win.work["224x224x3", 8])
    assert _reader("mfu.convnext_bulk").read(win) == pytest.approx(
        100 * flops / 0.1 / peaks["bf16_flops_per_s"])
    assert _reader("mfu.convnext_bulk").read(win) == \
        _reader("mfu.bulk").read(win)
