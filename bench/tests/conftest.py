import pytest


@pytest.fixture(autouse=True)
def _hermetic_plan_cache(tmp_path, monkeypatch):
    """Plan caches of the program go to a temp dir, never to the
    checkout's or the user's."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "repro_cache"))
    from repro.core import autotune, graph
    autotune.clear_cache()
    graph.clear_cache()
    yield
    autotune.clear_cache()
    graph.clear_cache()


class FakeClock:
    """Injectable clock (seconds) that moves ``step`` on every reading
    and by the full amount on ``sleep``."""

    def __init__(self, step: float = 5e-5):
        self.t = 100.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += dt


@pytest.fixture
def fake_clock():
    return FakeClock()
