"""The closed loop keeps its requests in flight whichever of them
finishes, and counts completions without the engine's lock."""
import threading
import time

import jax
import numpy as np

import _paths  # noqa: F401
from bench import serve


class _Engine:
    """Holds submitted requests and, every ``tick`` seconds, finishes the
    ``per_tick`` newest: the oldest request never finishes."""

    def __init__(self, tick: float, per_tick: int):
        self.docs_done = 0
        self.held = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._tick, self._per_tick = tick, per_tick
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit_async(self, doc, key=None):
        with self._lock:
            self.held.append(len(self.held) + self.docs_done + 1)
            return self.held[-1]

    def _loop(self):
        while not self._stop.wait(self._tick):
            with self._lock:
                n = min(self._per_tick, len(self.held) - 1)
                if n > 0:
                    del self.held[-n:]
                    self.docs_done += n

    def stop(self):
        self._stop.set()
        self._thread.join()


def _load(engine, n):
    docs = [np.arange(3, dtype=np.int32)]
    keys = np.asarray(jax.random.key_data(
        jax.random.split(jax.random.key(0), n)))
    return serve.Load(engine, docs, keys)


def test_closed_loop_replaces_any_finished_request():
    engine = _Engine(tick=0.01, per_tick=4)
    try:
        load = _load(engine, 100_000)
        closed = serve.Closed(load, outstanding=16, max_requests=100_000)
        closed.top_up()
        assert load.in_flight() == 16
        closed.ramp(timeout=5.0)
        closed.run(0.4)
    finally:
        engine.stop()
    assert len(load.keys) == len(load.sent) + 16  # keys made ahead
    assert load.finished() > 3 * 16  # refilled many times over
    assert 16 - 3 * 4 <= closed.low <= 16
    assert load.in_flight() <= 16
    assert 1 in engine.held  # the oldest never finished


def test_closed_loop_stops_at_max_requests():
    engine = _Engine(tick=0.005, per_tick=8)
    try:
        load = _load(engine, 40)
        closed = serve.Closed(load, outstanding=16, max_requests=40)
        closed.run(0.2)
    finally:
        engine.stop()
    assert len(load.sent) == 40
