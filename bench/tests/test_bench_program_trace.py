"""The program's spans and scopes as ``bench/program_trace.py`` reads them:
idle attribution and launches on hand-made events, the engine's spans in a
CPU profiler trace, the step's named scopes in its HLO, and a small trace
recorded on a v5e chip."""
import contextlib
import os
import re

import jax
import numpy as np
import pytest

import _paths
from bench import program_trace, trace
from bench.harness import Window
from bench.program_trace import ProgramTrace
from bench.trace import Event

DATA = os.path.join(_paths.ROOT, "bench", "tests", "data")
DEV = "/device:TPU:0"
CHILDREN = ("zen.engine.admit", "zen.engine.keys", "zen.engine.sweep",
            "zen.engine.finish")


def _trace():
    # window 0..100; tick A 5..50 (admit, sweep, finish), tick B 55..90
    # (keys, sweep); the device runs 10..20, 30..35, 60..70 in ticks and
    # 92..95 outside them
    modules = {DEV: [Event(10, 20, "jit_a"), Event(30, 35, "jit_b"),
                     Event(60, 70, "jit_c"), Event(92, 95, "jit_d")]}
    ops = {DEV: [Event(10, 20, "%fusion.1 = s32[8] fusion(s32[8] %a)")]}
    spans = [Event(5, 50, "zen.engine.tick"),
             Event(5, 15, "zen.engine.admit"),
             Event(25, 40, "zen.engine.sweep"),
             Event(40, 48, "zen.engine.finish"),
             Event(55, 90, "zen.engine.tick"),
             Event(55, 58, "zen.engine.keys"),
             Event(58, 65, "zen.engine.sweep"),
             Event(110, 120, "zen.engine.tick")]  # after the window
    return ProgramTrace(spans, [Event(0, 100, "bench.window")], modules, ops)


def test_idle_attributed_to_the_innermost_open_span():
    red = program_trace.reduce(_trace())
    assert red.window_s == pytest.approx(100e-9)
    # idle 0..10, 20..30, 35..60, 70..92, 95..100
    assert red.idle_s == pytest.approx(72e-9)
    by = {k: v * 1e9 for k, v in red.idle_by_span.items()}
    assert by == pytest.approx({
        "bench.window": 5 + 5 + 2 + 5,  # 0..5, 50..55, 90..92, 95..100
        "zen.engine.admit": 5,  # 5..10
        "zen.engine.tick": 5 + 2 + 20,  # 20..25, 48..50, 70..90
        "zen.engine.sweep": 5 + 5 + 2,  # 25..30, 35..40, 58..60
        "zen.engine.finish": 8,  # 40..48
        "zen.engine.keys": 3,  # 55..58
    })
    assert sum(red.idle_by_span.values()) == pytest.approx(red.idle_s)
    assert red.idle_in_tick_s == pytest.approx(55e-9)
    assert red.idle_on_spans_s() == pytest.approx(55e-9)
    assert red.idle_in_tick_share == pytest.approx(0.55)


def test_ticks_and_launches_inside_the_window():
    red = program_trace.reduce(_trace())
    assert red.ticks == 2
    assert red.tick_mean_s == pytest.approx(40e-9)  # (45 + 35) / 2
    assert red.launches_in_tick == 3
    assert red.launches_outside == 1
    assert red.launches_per_tick == pytest.approx(1.5)


def test_no_window_is_an_error():
    t = _trace()
    with pytest.raises(ValueError, match="bench.window"):
        program_trace.reduce(t._replace(windows=[]))


_HLO = """\
HloModule jit_step, is_scheduled=true

%fused_scatter (param_0: s32[8], param_1: s32[4]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  %reshape.7 = s32[4]{0} reshape(%param_1), \
metadata={op_name="jit(step)/zen.delta_counts/convert_element_type"}
  ROOT %scatter.1 = s32[8]{0} scatter(%param_0, %reshape.7)
}

%body (p: (s32[8])) -> (s32[8]) {
  %p = (s32[8]{0}) parameter(0)
  %dus.1 = s32[8]{0} dynamic-update-slice(%p)
  ROOT %tuple.1 = (s32[8]{0}) tuple(%dus.1)
}

ENTRY %main (a: s32[8], b: s32[4]) -> s32[8] {
  %a = s32[8]{0} parameter(0)
  %b = s32[4]{0} parameter(1)
  %fusion.3 = s32[8]{0} fusion(%a), kind=kLoop, calls=%fc.3, \
metadata={op_name="jit(step)/zen.sweep/jit(f)/zen.relayout/pad" \
source_file="ops.py" source_line=3}
  %sort.2 = s32[4]{0} sort(%b), dimensions={0}
  %fusion.4 = s32[8]{0} fusion(%fusion.3, %sort.2), kind=kCustom, \
calls=%fused_scatter
  %copy.2 = s32[8]{0} copy(%fusion.4)
  %while.1 = (s32[8]{0}) while(%a), body=%body, \
metadata={op_name="jit(step)/zen.sweep/while"}
  %neg.4 = s32[8]{0} negate(%a), metadata={op_name="jit(step)/neg"}
  ROOT %add.1 = s32[8]{0} add(%neg.4, %neg.4), \
metadata={op_name="jit(step)/zen.update/add"}
}
"""


def test_scope_map_takes_the_innermost_zen_scope():
    scopes = program_trace.scope_map(_HLO)
    assert scopes["fusion.3"] == "zen.relayout"
    assert scopes["add.1"] == "zen.update"
    assert scopes["reshape.7"] == "zen.delta_counts"


def test_scope_map_fills_what_compiler_passes_made():
    scopes = program_trace.scope_map(_HLO)
    # a fusion without metadata: its fused computation's scope
    assert scopes["fusion.4"] == "zen.delta_counts"
    # a sort without metadata: its user's; a copy with no scoped user:
    # its operand's; an op in a loop body: the loop's
    assert scopes["sort.2"] == "zen.delta_counts"
    assert scopes["copy.2"] == "zen.delta_counts"
    assert scopes["dus.1"] == "zen.sweep"
    # an op under no zen scope whose user is scoped takes the user's
    assert scopes["neg.4"] == "zen.update"
    assert "a" in scopes and "b" in scopes


def test_seconds_by_scope_keeps_the_remainder():
    op_s = {"%fusion.3 = s32[8] fusion(s32[8] %a)": 2.0,
            "%fusion.4 = s32[8] fusion(s32[8] %b)": 1.0,
            "%other.9 = s32[8] copy(s32[8] %x)": 0.5}
    by = program_trace.seconds_by_scope(op_s,
                                        program_trace.scope_map(_HLO))
    assert by == {"zen.relayout": 2.0, "zen.delta_counts": 1.0,
                  "unattributed": 0.5}
    assert sum(by.values()) == pytest.approx(sum(op_s.values()))


def _engine():
    from repro.core.types import LDAHyperParams
    from repro.serving import FrozenLDAModel, LDAEngine, LDAServeConfig

    k, w = 8, 40
    n_wk = np.random.default_rng(0).integers(0, 5, (w, k)).astype(np.int32)
    model = FrozenLDAModel(n_wk=jax.numpy.asarray(n_wk),
                           n_k=jax.numpy.asarray(n_wk.sum(0)),
                           hyper=LDAHyperParams(num_topics=k))
    return LDAEngine(model, LDAServeConfig(buckets=(8, 16), max_batch=4,
                                           num_sweeps=3))


def _nested(red_trace):
    ticks = [s for s in red_trace.spans if s.name == "zen.engine.tick"]
    children = [s for s in red_trace.spans if s.name in CHILDREN]
    return ticks, children, all(
        any(t.start <= c.start and c.end <= t.end for t in ticks)
        for c in children)


def test_engine_spans_in_a_cpu_profiler_trace(tmp_path):
    eng = _engine()
    eng.warm()
    docs = [np.arange(n) % 40 for n in (3, 5, 8, 12, 16, 7)]
    ticks0 = eng.ticks
    eng.start()
    try:
        with Window(str(tmp_path)):
            for t in [eng.submit_async(d) for d in docs]:
                eng.result(t, timeout=120)
    finally:
        eng.stop()
    tr = program_trace.load(trace.find_xplane(str(tmp_path)))
    ticks, children, nested = _nested(tr)
    assert {c.name for c in children} == set(CHILDREN)
    assert nested
    assert len(ticks) == eng.ticks - ticks0 > 0
    red = program_trace.reduce(tr)
    assert red.ticks == len(ticks)
    assert red.launches_in_tick == 0  # a CPU trace has no device plane
    assert red.idle_s == pytest.approx(red.window_s)


def _step_hlo():
    from repro.core.types import LDAHyperParams
    from repro.data import synthetic_lda_corpus
    from repro.train.session import RunConfig, TrainSession

    corpus, _ = synthetic_lda_corpus(seed=0, num_docs=12, num_words=30,
                                     num_topics=4, avg_doc_len=8)
    session = TrainSession(corpus, LDAHyperParams(num_topics=4), RunConfig())
    exe, _ = session.plan.compiled_step(session.init(jax.random.key(0)))
    return exe.as_text()


def _strip_metadata(hlo):
    """The program without its debug info: each instruction's metadata
    and the source-location tables that its stack frame ids point into."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*", "\n", hlo)


def test_step_scopes_change_only_the_hlo_metadata(monkeypatch):
    scoped = _step_hlo()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _step_hlo()
    scopes = set(program_trace.scope_map(scoped).values())
    assert {"zen.sweep", "zen.delta_counts", "zen.update"} <= scopes
    assert not program_trace.scope_map(plain)
    assert _strip_metadata(scoped) == _strip_metadata(plain)


def test_recorded_chip_trace_with_engine_spans_on_the_ticker_thread():
    path = os.path.join(DATA, "program.xplane.pb")
    tr = program_trace.load(path)
    ticks, children, nested = _nested(tr)
    assert {c.name for c in children} == set(CHILDREN)
    assert nested
    red = program_trace.reduce(tr)
    assert red.ticks == len(ticks) == 4
    assert red.launches_in_tick > 0
    assert 0 < red.idle_s < red.window_s
    assert sum(red.idle_by_span.values()) == pytest.approx(red.idle_s)
    assert red.idle_on_spans_s() > red.idle_s / 2
    # the same window and device as the benchmark's own reduction
    bench_red = trace.reduce(trace.load(path))
    assert red.window_s == pytest.approx(bench_red.window_s)
    assert red.idle_s == pytest.approx(bench_red.window_s - bench_red.busy_s,
                                       rel=1e-6)
