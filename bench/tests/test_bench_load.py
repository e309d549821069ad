"""The closed loop keeps its requests in flight whichever of them
finishes, and counts completions without the engine's lock.

The fake engine has no thread of its own: it finishes requests only when
the loop sleeps, on a clock of the test's own that stands in for
``bench/serve.py``'s ``time``. So no tick can fall between a top-up's
submissions and the keys it makes ahead, however loaded the machine."""
import jax
import numpy as np
import pytest

import _paths  # noqa: F401
from bench import serve


class _Engine:
    """Holds submitted requests and, at every ``tick`` seconds of the
    test's clock, finishes the ``per_tick`` newest: the oldest request
    never finishes."""

    def __init__(self, tick: float, per_tick: int):
        self.docs_done = 0
        self.held = []
        self._tick, self._per_tick = tick, per_tick
        self._ticks = 0

    def submit_async(self, doc, key=None):
        self.held.append(len(self.held) + self.docs_done + 1)
        return self.held[-1]

    def advance(self, now: float) -> None:
        """Run every tick due by ``now``."""
        while now >= (self._ticks + 1) * self._tick - 1e-9:
            self._ticks += 1
            n = min(self._per_tick, len(self.held) - 1)
            if n > 0:
                del self.held[-n:]
                self.docs_done += n


class _Clock:
    """``monotonic`` and ``sleep`` for ``bench/serve.py``: a sleep moves
    the clock on and runs the engine's ticks that fall due."""

    def __init__(self, engine: _Engine):
        self.now, self.engine = 0.0, engine

    def monotonic(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds
        self.engine.advance(self.now)


@pytest.fixture
def engine_on_clock(monkeypatch):
    def make(tick: float, per_tick: int) -> _Engine:
        engine = _Engine(tick, per_tick)
        monkeypatch.setattr(serve, "time", _Clock(engine))
        return engine

    return make


def _load(engine, n):
    docs = [np.arange(3, dtype=np.int32)]
    keys = np.asarray(jax.random.key_data(
        jax.random.split(jax.random.key(0), n)))
    return serve.Load(engine, docs, keys)


def test_closed_loop_replaces_any_finished_request(engine_on_clock):
    engine = engine_on_clock(tick=0.01, per_tick=4)
    load = _load(engine, 100_000)
    closed = serve.Closed(load, outstanding=16, max_requests=100_000)
    closed.top_up()
    assert load.in_flight() == 16
    closed.ramp(timeout=5.0)
    closed.run(0.4)
    assert len(load.keys) == len(load.sent) + 16  # keys made ahead
    assert load.finished() > 3 * 16  # refilled many times over
    assert 16 - 3 * 4 <= closed.low <= 16
    assert load.in_flight() <= 16
    assert 1 in engine.held  # the oldest never finished


def test_closed_loop_stops_at_max_requests(engine_on_clock):
    engine = engine_on_clock(tick=0.005, per_tick=8)
    load = _load(engine, 40)
    closed = serve.Closed(load, outstanding=16, max_requests=40)
    closed.run(0.2)
    assert len(load.sent) == 40
