"""The load generator: one serve loop over the frontend's public API,
fed by a traffic mix read from ``bench/traffic/<mix>.json``.

A mix is data only.  Its ``"loop"`` key picks one of two arrival kinds:

``closed``  ``clients`` clients, each with one request of
            ``images_per_request`` images outstanding; a client sends
            its next request as soon as its last one completes.  The
            window ends at ``seconds``: nothing is sent after it, and
            what is in flight then is drained.
``open``    Poisson arrivals at a mean of ``rate_per_s``.  The gaps are
            the exponential distribution's quantiles at ``(k + 0.5) /
            K``, shuffled by the seed and scaled to fill the window; an
            optional ``rate_cycle`` (a list of ``[seconds, relative
            rate]``, repeated over the window) bends them in time, so a
            burst is data too.  The sizes are ``sizes`` (a list of
            ``[images, weight]``).  Every scheduled request is sent
            (late, if the loop lags) and waited for.

Both kinds take ``image_sizes`` (a list of ``[pixels, weight]``; by
default the configuration's ``image_size`` alone): the resolution of
each request, served by a geometry of the frontend of its own.  Sizes
and resolutions are apportioned to the requests by largest remainders
and shuffled by the seed, so every seed sends the same requests and
gaps, in another order.

The serve loop is the simplest one a user of ``AsyncServeFrontend``
would write::

    while work remains:
        submit every request that is due
        poll()
        if nothing waits for admission but requests are in flight:
            flush()            # poll() harvests only when pipeline_depth
                               # batches are in flight, so a lone batch
                               # would otherwise wait for the next arrival
        elif nothing is in flight:
            sleep until the next request is due

Images come from a pool per resolution made from the seed on the host;
a request of ``n`` images is a view of ``n`` consecutive pool images,
taken round-robin, so packing and host-to-device transfer stay on the
path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

#: requests over which a closed loop's resolutions are apportioned and
#: then repeated
CLOSED_BLOCK = 64


@dataclasses.dataclass
class Sent:
    """One request as the client saw it (times on the loop's clock)."""
    rid: int
    first: int           # index of its first image in its size's pool
    images: int
    t_sched: float
    size: int = 0        # image height and width, pixels
    t_submit: float = math.nan
    t_done: float = math.nan
    status: str = "unsent"
    out: Optional[np.ndarray] = None


class _Pool:
    """Round-robin views of consecutive images of one pool."""

    def __init__(self, images: np.ndarray):
        self.images = images
        self.next = 0

    def take(self, n: int):
        if self.next + n > len(self.images):
            self.next = 0
        first = self.next
        self.next += n
        return first, self.images[first:first + n]


def size_pairs(mix: Dict, default: int) -> List[list]:
    """The mix's ``[pixels, weight]`` resolutions; ``default`` alone if
    it names none."""
    return mix.get("image_sizes", [[default, 1]])


def deal(pairs: Sequence, k: int, rng: np.random.Generator) -> List[int]:
    """``k`` values from ``[value, weight]`` pairs, apportioned by
    largest remainders and shuffled by ``rng``."""
    values = np.array([v for v, _ in pairs], int)
    want = np.array([w for _, w in pairs], float)
    want *= k / want.sum()
    counts = np.floor(want).astype(int)
    counts[np.argsort(counts - want, kind="stable")[:k - counts.sum()]] += 1
    return [int(v) for v in rng.permutation(np.repeat(values, counts))]


class ClosedSource:
    def __init__(self, mix: Dict, t0: float, t_end: float, size: int,
                 rng: np.random.Generator):
        self.per_request = int(mix["images_per_request"])
        self.t_end = t_end
        self.ready = [t0] * int(mix["clients"])     # clients due to send
        self.sizes = deal(size_pairs(mix, size), CLOSED_BLOCK, rng)
        self.n = 0

    def due(self, now: float) -> List[tuple]:
        if now >= self.t_end:
            return []
        out = []
        for t in self.ready:
            out.append((t, self.per_request,
                        self.sizes[self.n % len(self.sizes)]))
            self.n += 1
        self.ready = []
        return out

    def done(self, sent: Sent, now: float) -> None:
        self.ready.append(now)

    def next_time(self) -> Optional[float]:
        return None

    def exhausted(self, now: float) -> bool:
        return now >= self.t_end


def _cycle_time(tau: np.ndarray, cycle: Sequence, seconds: float,
                ) -> tuple:
    """Wall times at which a rate that follows ``cycle`` (``[seconds,
    relative rate]`` pairs, scaled to a mean of 1 and repeated) has
    delivered ``tau`` seconds' worth of the mean rate; also the worth
    of the whole window."""
    dur = np.array([d for d, _ in cycle], float)
    rel = np.array([r for _, r in cycle], float)
    if (dur <= 0).any() or (rel <= 0).any():
        raise ValueError(f"rate_cycle needs positive seconds and rates; "
                         f"got {cycle}")
    rel /= dur @ rel / dur.sum()
    n = int(math.ceil(seconds / dur.sum())) + 1
    wall = np.concatenate([[0.0], np.cumsum(np.tile(dur, n))])
    worth = np.concatenate([[0.0], np.cumsum(np.tile(dur * rel, n))])
    return np.interp(tau, worth, wall), float(np.interp(seconds, wall, worth))


class OpenSource:
    def __init__(self, mix: Dict, t0: float, seconds: float, size: int,
                 rng: np.random.Generator):
        rate = float(mix["rate_per_s"])
        k = max(1, int(round(rate * seconds)))
        u = (np.arange(k) + 0.5) / k
        self.gaps = rng.permutation(-np.log1p(-u))
        cycle = mix.get("rate_cycle", [[1.0, 1.0]])
        _, worth = _cycle_time(np.zeros(1), cycle, seconds)
        self.gaps *= worth / self.gaps.sum()
        tau = np.concatenate([[0.0], np.cumsum(self.gaps)[:-1]])
        self.times = list(t0 + _cycle_time(tau, cycle, seconds)[0])
        self.sizes = deal(mix["sizes"], k, rng)
        self.res = deal(size_pairs(mix, size), k, rng)
        self.i = 0

    def due(self, now: float) -> List[tuple]:
        j = self.i
        while j < len(self.times) and self.times[j] <= now:
            j += 1
        out = [(self.times[i], self.sizes[i], self.res[i])
               for i in range(self.i, j)]
        self.i = j
        return out

    def done(self, sent: Sent, now: float) -> None:
        pass

    def next_time(self) -> Optional[float]:
        return self.times[self.i] if self.i < len(self.times) else None

    def exhausted(self, now: float) -> bool:
        return self.i >= len(self.times)


def make_source(mix: Dict, t0: float, seconds: float, size: int,
                rng: np.random.Generator):
    """The mix's arrivals over a window of ``seconds`` from ``t0``;
    ``size`` is the resolution of a mix that names none."""
    if mix["loop"] == "closed":
        return ClosedSource(mix, t0, t0 + seconds, size, rng)
    if mix["loop"] == "open":
        return OpenSource(mix, t0, seconds, size, rng)
    raise ValueError(f"traffic loop must be 'closed' or 'open'; "
                     f"got {mix['loop']!r}")


def serve(fe, source, pools: Dict[int, np.ndarray], make_request: Callable,
          clock: Callable[[], float], sleep: Callable[[float], None],
          span: Callable = lambda name: contextlib.nullcontext()
          ) -> List[Sent]:
    """Drive ``fe`` with ``source`` until the source is exhausted and
    every sent request has come back; ``pools`` maps each resolution to
    its images.  Returns every request sent."""
    images = {s: _Pool(p) for s, p in pools.items()}
    sent: List[Sent] = []
    open_: Dict[int, Sent] = {}
    rid = 0

    def finish(done, now):
        for req in done:
            s = open_.pop(req.rid)
            s.t_done, s.status, s.out = now, req.status, req.out
            source.done(s, now)

    while True:
        now = clock()
        due = source.due(now)
        if due:
            with span("bench.submit"):
                for t_sched, n, size in due:
                    first, view = images[size].take(n)
                    s = Sent(rid, first, n, t_sched, size)
                    s.t_submit = clock()
                    fe.submit(make_request(rid, view))
                    open_[rid] = s
                    sent.append(s)
                    rid += 1
        if not open_:
            if source.exhausted(clock()):
                return sent
            nxt = source.next_time()
            if nxt is not None:
                with span("bench.sleep"):
                    sleep(max(0.0, nxt - clock()))
            continue
        with span("bench.poll"):
            done = fe.poll()
        finish(done, clock())
        if open_ and not fe.pending_counts():
            with span("bench.flush"):
                done = fe.flush()
            finish(done, clock())
