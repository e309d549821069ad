"""``MeshPlan`` on a (2, 2) mesh of four CPU devices, through the normal
path (``TrainSession`` -> ``MeshPlan`` -> ``make_dist_step``):

* ``compiled_step(state)`` returns ``(exe, args)``; ``step`` runs that
  program, compiled once however many steps run, and reaches the state
  that the jitted step reaches;
* the step, its compile, the partition and the init are spans of a
  profiler trace;
* the step's HLO carries the named scopes ``zen.sweep``, ``zen.relayout``,
  ``zen.delta_counts``, ``zen.exchange`` and ``zen.update``, and without
  its metadata is the program that the unscoped step compiles to;
* the initial topics are those an eager ``randint`` of the key draws.

The device count is fixed at JAX's first start, so the cases run in one
child process with four host devices, which prints one JSON line.
"""
import json
import os
import re
import subprocess
import sys

import pytest

from helpers import REPO

SCOPES = ["zen.delta_counts", "zen.exchange", "zen.relayout", "zen.sweep",
          "zen.update"]
SPANS = ["zen.mesh.init", "zen.mesh.partition", "zen.train.compile",
         "zen.train.step"]
STEPS = 3


def _strip_metadata(hlo):
    """The program without its debug info: each instruction's metadata
    and the source-location tables that its stack frame ids point into."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*", "\n", hlo)


def _host_span_names(trace_dir):
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _child(trace_dir) -> None:
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.types import LDAHyperParams
    from repro.data import synthetic_lda_corpus
    from repro.train.session import RunConfig, TrainSession

    corpus, _ = synthetic_lda_corpus(seed=0, num_docs=40, num_words=90,
                                     num_topics=6, avg_doc_len=20)
    hyper = LDAHyperParams(num_topics=6)
    cfg = RunConfig(algorithm="zen_pallas", kernels="on", mesh_shape=(2, 2))
    key = jax.random.key(3)
    out = {}

    jax.profiler.start_trace(trace_dir)
    session = TrainSession(corpus, hyper, cfg)
    plan = session.plan
    state = session.init(key)
    want = jax.random.randint(key, plan.grid.word.shape, 0, 6,
                              dtype=jnp.int32)
    out["init_topics_eager"] = bool(
        np.array_equal(np.asarray(state.topic), np.asarray(want))
        and np.array_equal(np.asarray(state.prev_topic), np.asarray(want)))
    out["compiles_before"] = plan.compiles
    exe, args = plan.compiled_step(state)
    out["args"] = [args[0] is state, args[1] is plan._data]
    out["exe"] = [callable(getattr(exe, n, None))
                  for n in ("memory_analysis", "as_text")]
    hlo = exe.as_text()
    for _ in range(STEPS):
        state = session.step(state)
    jax.block_until_ready(state.topic)
    jax.profiler.stop_trace()
    out["compiles"] = plan.compiles
    out["again"] = plan.compiled_step(state)[0] is exe

    # the same steps through the jitted step itself
    other = TrainSession(corpus, hyper, cfg).plan
    ref = other.init(key)
    for _ in range(STEPS):
        ref = other._step_fn(ref, other._data)
    out["same_state"] = {
        f: bool(np.array_equal(np.asarray(getattr(state, f)),
                               np.asarray(getattr(ref, f))))
        for f in ("topic", "prev_topic", "n_wk", "n_kd", "n_k",
                  "stale_iters", "same_count", "iteration")}
    out["spans"] = sorted({n for n in _host_span_names(trace_dir)
                           if n.startswith("zen.")})
    out["scopes"] = sorted(set(re.findall(r"zen\.[a-z_]+",
                                          " ".join(re.findall(
                                              r'op_name="([^"]*)"', hlo)))))

    # the unscoped step (the kernel wrappers' traces are cached)
    jax.named_scope = lambda name: contextlib.nullcontext()
    jax.clear_caches()
    plain_plan = TrainSession(corpus, hyper, cfg).plan
    plain = plain_plan.compiled_step(plain_plan.init(key))[0].as_text()
    out["plain_has_scopes"] = "zen." in plain
    out["stripped_equal"] = _strip_metadata(hlo) == _strip_metadata(plain)
    print("CASE " + json.dumps(out), flush=True)


@pytest.fixture(scope="module")
def case(tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("mesh_trace"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = ("import sys; sys.path.insert(0, {!r}); "
            "import test_mesh_plan; test_mesh_plan._child({!r})").format(
                os.path.dirname(os.path.abspath(__file__)), trace_dir)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(x for x in proc.stdout.splitlines() if x.startswith("CASE "))
    return json.loads(line[5:])


def test_compiled_step_returns_the_program_and_its_arguments(case):
    assert case["compiles_before"] == 0
    assert case["args"] == [True, True]
    assert case["exe"] == [True, True]


def test_steps_compile_once_and_reach_the_jitted_steps_state(case):
    assert case["compiles"] == 1
    assert case["again"]
    assert all(case["same_state"].values()), case["same_state"]


def test_step_compile_partition_and_init_are_spans(case):
    assert set(SPANS) <= set(case["spans"]), case["spans"]


def test_step_carries_the_named_scopes(case):
    assert set(SCOPES) <= set(case["scopes"]), case["scopes"]


def test_scopes_change_only_the_hlo_metadata(case):
    assert not case["plain_has_scopes"]
    assert case["stripped_equal"]


def test_initial_topics_are_an_eager_randint_of_the_key(case):
    assert case["init_topics_eager"]
