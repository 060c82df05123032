"""A whole run with the served path broken underneath must come out
not correct: an answer altered where it is produced, and answers handed
to the wrong images."""
import numpy as np

from repro.serve import cnn, frontend

from . import _tiny


def test_an_altered_answer_is_caught(monkeypatch):
    fn = cnn.BucketPrograms.fn

    def altered(self, b):
        f = fn(self, b)
        return lambda p, x: f(p, x).at[:, 0].add(0.05)
    monkeypatch.setattr(cnn.BucketPrograms, "fn", altered)
    res = _tiny.run("resnet50_bulk", seed=21)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > \
        res["checks"]["logit_err"]["limit"]


def test_answers_scattered_to_the_wrong_images_are_caught(monkeypatch):
    scatter = frontend.scatter_outputs
    monkeypatch.setattr(frontend, "scatter_outputs",
                        lambda chunk, y: scatter(chunk, np.roll(y, 1, 0)))
    res = _tiny.run("squeezenet1_0_bulk", seed=22)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > \
        res["checks"]["logit_err"]["limit"]
