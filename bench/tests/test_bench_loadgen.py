"""The serve loop of ``bench/loadgen.py`` over each traffic file, on
the CPU at a tiny size, with a fake clock."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import loadgen
from repro.models.cnn import SimpleCNN
from repro.serve import AsyncServeFrontend, ServeRequest

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SHAPE = (8, 8, 3)


def _mix(name, **override):
    return dict(json.loads((TRAFFIC / f"{name}.json").read_text()),
                **override)


class Recorder:
    """The frontend, with a log of the loop's calls and of the state in
    which ``flush`` was called."""

    def __init__(self, fe):
        self.fe = fe
        self.calls = []
        self.open = 0
        self.max_open = 0

    def submit(self, req):
        self.open += 1
        self.max_open = max(self.max_open, self.open)
        self.calls.append("submit")
        self.fe.submit(req)

    def poll(self):
        self.calls.append("poll")
        done = self.fe.poll()
        self.open -= len(done)
        return done

    def flush(self):
        assert not self.fe.pending_counts(), "flush with requests waiting"
        self.calls.append("flush")
        done = self.fe.flush()
        assert self.fe.stats()["inflight"] == 0
        self.open -= len(done)
        return done

    def pending_counts(self):
        return self.fe.pending_counts()


def _serve(mix, clock, seconds, seed=0):
    model = SimpleCNN([(3, 3, 6, 2), (1, 1, 4, 1)], num_classes=3)
    params = model.init(jax.random.PRNGKey(0))
    fe = AsyncServeFrontend(model, params, {SHAPE: tuple(mix["buckets"])},
                            max_wait_ms=mix["max_wait_ms"], clock=clock)
    rec = Recorder(fe)
    pool = np.random.default_rng(seed).standard_normal(
        (mix["pool_images"],) + SHAPE, dtype=np.float32)
    rng = np.random.default_rng(seed)
    sent = loadgen.serve(
        rec, loadgen.make_source(mix, clock(), seconds, SHAPE[0], rng),
        {SHAPE[0]: pool},
        lambda rid, x: ServeRequest(rid=rid, images=x), clock, clock.sleep)
    ref = np.asarray(model.apply(params, pool, algorithm="lax"))
    return sent, rec, pool, ref


def test_bulk_closed_loop_keeps_one_request_per_client(fake_clock):
    mix = _mix("bulk", pool_images=40)
    sent, rec, _, ref = _serve(mix, fake_clock, 0.01)
    assert len(sent) > mix["clients"]
    assert rec.max_open == mix["clients"]
    assert all(s.status == "served" for s in sent)
    assert all(s.images == mix["images_per_request"] for s in sent)
    # nothing is sent after the window
    assert max(s.t_submit for s in sent) < sent[0].t_submit + 0.01
    for s in sent:          # every answer is its own images' answer
        np.testing.assert_allclose(s.out, ref[s.first:s.first + s.images],
                                   rtol=1e-5, atol=1e-5)


def test_interactive_open_loop_sends_every_request_and_flushes(fake_clock):
    mix = _mix("interactive", rate_per_s=2000, pool_images=24)
    sent, rec, _, ref = _serve(mix, fake_clock, 0.05)
    assert len(sent) == 100
    assert all(s.status == "served" for s in sent)
    assert all(s.t_submit >= s.t_sched for s in sent)
    assert all(s.t_done > s.t_submit for s in sent)
    # a lone batch is flushed rather than left for the next arrival
    assert "flush" in rec.calls
    for s in sent:
        np.testing.assert_allclose(s.out, ref[s.first:s.first + s.images],
                                   rtol=1e-5, atol=1e-5)


def test_open_loop_seeds_send_the_same_work_in_another_order():
    mix = _mix("interactive", rate_per_s=300)
    a = loadgen.OpenSource(mix, 0.0, 10.0, 224, np.random.default_rng(1))
    b = loadgen.OpenSource(mix, 0.0, 10.0, 224, np.random.default_rng(2))
    assert len(a.times) == len(b.times) == 3000
    assert sorted(a.sizes) == sorted(b.sizes) and a.sizes != b.sizes
    assert np.allclose(sorted(a.gaps), sorted(b.gaps))
    assert a.times[-1] < 10.0
    assert sum(s == 1 for s in a.sizes) == 2700
    assert set(a.sizes) == set(range(1, 9))
    assert set(a.res) == {224}


def test_a_rate_cycle_bursts_at_the_same_mean_rate():
    """4x the rate for 1 s in every 5 s, as data: the same number of
    requests, four times as dense inside the bursts."""
    mix = _mix("interactive", rate_per_s=200, rate_cycle=[[1, 4], [4, 1]])
    src = loadgen.OpenSource(mix, 0.0, 10.0, 224, np.random.default_rng(3))
    t = np.array(src.times)
    assert len(t) == 2000 and t.min() >= 0 and t.max() < 10.0
    burst = ((t % 5) < 1).sum()
    assert burst / 2 == pytest.approx(4 * (len(t) - burst) / 8, rel=0.1)


def test_image_sizes_are_dealt_to_requests_by_weight():
    mix = _mix("interactive", rate_per_s=100,
               image_sizes=[[160, 1], [224, 2], [288, 1]])
    src = loadgen.OpenSource(mix, 0.0, 10.0, 224, np.random.default_rng(4))
    assert [src.res.count(s) for s in (160, 224, 288)] == [250, 500, 250]
    closed = loadgen.ClosedSource(dict(_mix("bulk"), **mix), 0.0, 1.0, 224,
                                  np.random.default_rng(4))
    due = closed.due(0.0)
    assert len(due) == 16 and {r for _, _, r in due} <= {160, 224, 288}
    assert sorted(closed.sizes) == sorted([160] * 16 + [224] * 32
                                          + [288] * 16)


def test_unknown_loop_kind_is_refused():
    with pytest.raises(ValueError, match="closed' or 'open"):
        loadgen.make_source({"loop": "burst"}, 0.0, 1.0, 224,
                            np.random.default_rng(0))


def test_a_rate_cycle_needs_positive_rates():
    with pytest.raises(ValueError, match="positive"):
        loadgen.OpenSource(_mix("interactive", rate_cycle=[[1, 0]]), 0.0,
                           1.0, 224, np.random.default_rng(0))
