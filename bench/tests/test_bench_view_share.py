"""The ``view_share.bulk`` reader: the frontend's feed-path counters
(``BatchTrace.slices``/``view_slices``) over a window's batches."""
import importlib.util
import types
from pathlib import Path

import numpy as np
import pytest

from bench import run

READER = Path(__file__).resolve().parents[1] / "metrics" / "view_share.bulk.py"


def _read(batches):
    spec = importlib.util.spec_from_file_location("reader_view_share",
                                                  READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    win = run.Window(1.0, 0.0, 1.0, [], batches, [], None, {}, {}, 1.0)
    return mod.read(win)


def test_view_share_reads_nothing_without_the_counters():
    """A program that counts no slices (the parent of the feed path)
    reads None, and nothing raises; so does an empty window."""
    batch = types.SimpleNamespace(geometry="g", bucket=8, units=8,
                                  transfer_t0=0.0, transfer_t1=0.1)
    assert _read([batch]) is None
    assert _read([]) is None


def test_view_share_is_views_over_slices_summed_over_batches():
    from repro.serve.telemetry import BatchTrace

    def batch(slices, views):
        return BatchTrace(geometry="g", bucket=32, units=32, padded=0,
                          transfer_t0=0.0, transfer_t1=0.1, dispatch_t=0.2,
                          slices=slices, view_slices=views)
    # two fed batches (4 slices; 4 and 3 views) and one packed batch
    assert _read([batch(4, 4), batch(4, 3), batch(1, 0)]) == pytest.approx(
        100.0 * 7 / 9)


def test_view_share_of_aligned_requests_served_through_the_feed_path(
        monkeypatch):
    """Served end to end: 2-image requests through bucket 4 of a tiny
    net, every batch fed, every slice a request's own array."""
    import jax

    from repro.models.cnn import SimpleCNN
    from repro.serve import frontend
    monkeypatch.setattr(frontend, "FEED_BYTES", 0)
    model = SimpleCNN([(3, 3, 4, 1)], num_classes=3)
    fe = frontend.AsyncServeFrontend(
        model, model.init(jax.random.PRNGKey(0)), {(8, 8, 3): (2, 4)})
    fe.warmup()
    rng = np.random.default_rng(0)
    for i in range(4):
        fe.submit(frontend.ServeRequest(rid=i, images=rng.standard_normal(
            (2, 8, 8, 3), dtype=np.float32)))
    fe.run()
    fe.close()
    assert _read(fe.telemetry.batches) == pytest.approx(100.0)
