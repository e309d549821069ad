"""The program's own spans and counters (DESIGN.md §8.5).

* ``repro.observe.span`` and ``SpanTimer`` write ``zen.*`` spans into a
  profiler trace, and so do the training step and its compiles;
* the engine counts its ticks and the real and swept slot tokens of every
  bucket sweep exactly, in throughput and in latency mode, and the
  ``serve_window`` record reports the window's pad share from them;
* ``SingleBoxPlan.compiled_step`` counts one compile per new signature
  and none on a cache hit.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.types import LDAHyperParams
from repro.observe import MetricsRegistry, ServeTelemetry, span
from repro.observe.metrics import read_jsonl
from repro.serving import FrozenLDAModel, LDAEngine, LDAServeConfig
from repro.train.session import RunConfig, TrainSession


def _host_span_names(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            names += [e.name for line in plane.lines for e in line.events]
    return names


def test_spans_timers_and_train_step_reach_the_profiler_trace(
        tmp_path, tiny_corpus, tiny_hyper):
    session = TrainSession(tiny_corpus, tiny_hyper, RunConfig())
    state = session.init(jax.random.key(0))
    reg = MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("probe"):
            pass
        with reg.timer("jit_rebuild"):
            state = session.step(state)
        jax.block_until_ready(state.topic)
    finally:
        jax.profiler.stop_trace()
    names = _host_span_names(str(tmp_path))
    for name in ("zen.probe", "zen.jit_rebuild", "zen.train.step",
                 "zen.train.compile"):
        assert name in names, name
    assert reg.histogram("jit_rebuild").count == 1


def test_compiles_counts_new_signatures_only(tiny_corpus, tiny_hyper):
    plan = TrainSession(tiny_corpus, tiny_hyper, RunConfig()).plan
    state = plan.init(jax.random.key(0))
    assert plan.compiles == 0
    exe, _ = plan.compiled_step(state)
    assert plan.compiles == 1
    state = plan.step(state)
    state = plan.step(state)
    again, _ = plan.compiled_step(state)
    assert again is exe and plan.compiles == 1
    assert plan.apply_row_pads(8, 8)  # new knobs: a new signature
    plan.step(state)
    assert plan.compiles == 2
    plan.compiled_step(state)
    assert plan.compiles == 2


def _engine(mode, **kw):
    k, w = 4, 40
    n_wk = np.random.default_rng(0).integers(0, 5, (w, k)).astype(np.int32)
    model = FrozenLDAModel(n_wk=jax.numpy.asarray(n_wk),
                           n_k=jax.numpy.asarray(n_wk.sum(0)),
                           hyper=LDAHyperParams(num_topics=k))
    return LDAEngine(model, LDAServeConfig(buckets=(8, 16), max_batch=4,
                                           num_sweeps=3, mode=mode, **kw))


# documents of 3 and 5 tokens share the 8-bucket, 12 takes the 16-bucket
_LENGTHS = (3, 5, 12)


@pytest.mark.parametrize("mode,ticks", [("throughput", 3), ("latency", 1)])
def test_engine_counts_ticks_and_swept_tokens_exactly(mode, ticks):
    eng = _engine(mode)
    for n in _LENGTHS:
        eng.submit(np.arange(n) % 40)
    done = eng.run_until_done()
    assert len(done) == len(_LENGTHS)
    assert eng.ticks == ticks
    # every tick sweeps both buckets, every slot of each
    assert eng.sweeps_run == 2 * ticks
    assert eng.tokens_swept == ticks * sum(_LENGTHS)
    assert eng.slot_tokens_swept == ticks * 4 * (8 + 16)
    pad = 1 - eng.tokens_swept / eng.slot_tokens_swept
    assert pad == pytest.approx(1 - 20 / 96)


def test_serve_window_reports_the_windows_pad_share():
    tel = ServeTelemetry(MetricsRegistry(), window_ticks=2,
                         window_arrivals=10_000)
    knobs = dict(queue_depth=0, occupancy=0, finished=[], spills_total=0,
                 tick_period=0.001, max_slot_wait=0, bucket_widths=(8,),
                 model_version=0)
    # cumulative (tokens, slot tokens) after each tick
    totals = [(10, 40), (20, 80), (50, 120), (50, 160)]
    windows = [tel.record_tick(tokens_swept=t, slot_tokens_swept=s, **knobs)
               for t, s in totals]
    assert windows[0] is None and windows[2] is None
    assert windows[1]["pad_share"] == pytest.approx(1 - 20 / 80)
    assert windows[3]["pad_share"] == pytest.approx(1 - 30 / 80)


def test_serve_window_through_the_engine(tmp_path):
    path = str(tmp_path / "serve.jsonl")
    eng = _engine("throughput", metrics_out=path, autopilot_window=3)
    for n in _LENGTHS:
        eng.submit(np.arange(n) % 40)
    eng.run_until_done()
    # three arrivals close the first window after the first tick
    windows = [r for r in read_jsonl(path) if r["kind"] == "serve_window"]
    assert windows[0]["ticks"] == 1
    assert windows[0]["pad_share"] == pytest.approx(1 - 20 / 96)
