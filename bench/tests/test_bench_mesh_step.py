"""The mesh step's delta exchange and smoothing mass, as the benchmark
reads them.

* ``train_exchange_ms`` sums the ops of scope ``zen.exchange`` per step
  and chip, on a trace recorded on a v5e chip with a hand-made scope map;
* ``program_trace.scope_map`` of the (2, 2) mesh step puts every
  all-reduce in ``zen.exchange``;
* on a skewed corpus whose grid pads the vocabulary to about twice W, the
  mesh step's draws are the reference's argmax with W in the smoothing
  mass (``bench/train.py``'s own mesh check, limit 1e-6 nats).

The mesh cases run in one child process with four host devices (the
device count is fixed at JAX's first start), one JSON line each.
"""
import json
import os
import re
import subprocess
import sys

import pytest

import _paths
from bench import run, trace

DATA = os.path.join(_paths.ROOT, "bench", "tests", "data")
SEED = 2**31 + 4099
STEPS = 3
W, D, K = 64, 48, 8
GAP_LIMIT = 1e-6
_ALL_REDUCE = re.compile(
    r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=.*?\sall-reduce(?:-start)?\(")


def _ctx(red, chips, scopes):
    cfg = run.load_cell("train-nytimes-2x2")[2]
    return {"trace": red, "steps": STEPS, "tokens_per_step": 1_000_000,
            "window_s": 1.0, "tokens_per_s": 3_000_000.0, "cfg": cfg,
            "peaks": None, "chips": chips, "scopes": scopes}


def test_exchange_reader_sums_its_scope():
    red = trace.reduce(trace.load(os.path.join(DATA, "program.xplane.pb")))
    read = run.metric_reader("train_exchange_ms").read
    names = sorted({trace.op_name(k) for k in red.op_s})
    scopes = {n: "zen.exchange" if n.startswith("copy") else "zen.sweep"
              for n in names}
    want = sum(v for k, v in red.op_s.items()
               if trace.op_name(k).startswith("copy"))
    assert want > 0
    for chips in (1, 4):
        assert read(_ctx(red, chips, scopes)) == pytest.approx(
            want / STEPS * 1e3 / chips, rel=1e-12)
    # no exchange (the single-box step); no scope map; no step scope
    for no in ({n: "zen.sweep" for n in names}, None,
               {n: "zen.exchange" for n in names}):
        assert read(_ctx(red, 1, no)) is None


def _skewed_corpus():
    """Half the tokens on word 0: an LPT split gives it a column of its
    own and the other 63 words the other, so the grid pads W to 126."""
    import numpy as np

    rng = np.random.default_rng(7)
    doc = np.repeat(np.arange(D), rng.integers(20, 120, D)).astype(np.int32)
    word = np.where(rng.random(doc.size) < 0.5, 0,
                    rng.integers(1, W, doc.size)).astype(np.int32)
    return word, doc


def _child() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import program_trace, reference, train
    from repro.core.types import Corpus, LDAHyperParams
    from repro.train.session import RunConfig, TrainSession

    cfg = {"num_words": W, "num_docs": D, "num_topics": K,
           "token_chunk": 512, "alpha": 0.1, "beta": 0.5,
           "alpha_prime": 1.0, "asymmetric_alpha": True}
    hyper = reference.hyper(cfg)
    word, doc = _skewed_corpus()
    session = TrainSession(
        Corpus(word=jnp.asarray(word), doc=jnp.asarray(doc), num_words=W,
               num_docs=D),
        LDAHyperParams(num_topics=K, **hyper),
        RunConfig(algorithm="zen_pallas", token_chunk=cfg["token_chunk"],
                  mesh_shape=(2, 2)))
    grid = session.plan.grid
    state = session.init(reference.seed_key(SEED))
    rng = jax.random.wrap_key_data(np.asarray(jax.random.key_data(state.rng)))

    try:
        hlo = session.plan.compiled_step(state)[0].as_text()
        scopes = program_trace.scope_map(hlo)
        reduces = [m.group(1) for m in map(_ALL_REDUCE.match,
                                           hlo.splitlines()) if m]
        line = {"all_reduces": {r: scopes.get(r) for r in reduces}}
    except Exception as e:  # a crash is reported as this case's
        line = {"error": repr(e)}
    print("CASE " + json.dumps(dict(line, case="scopes")), flush=True)

    kept = [np.asarray(state.topic)]
    for _ in range(2):
        state = session.step(state)
        kept.append(np.asarray(state.topic))
    counts = tuple(np.asarray(x) for x in (state.n_wk, state.n_kd,
                                           state.n_k))
    limits = {"draw_gap_nats": GAP_LIMIT, "count_mismatch": 0,
              "grid_token_mismatch": 0}
    checks, _, found = train._check_mesh(
        cfg, word, doc, grid, rng, kept, counts, kept[-1], 1024,
        tuple(sorted(hyper.items())), limits, jax.devices()[:4])
    line = {"checks": {c.name: c.value for c in checks},
            "not_argmax_tokens": found["not_argmax_tokens"],
            "padded_words": int(grid.words_per_shard * grid.model_parallel)}
    print("CASE " + json.dumps(dict(line, case="smoothing")), flush=True)


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import sys; sys.path[:0] = [{!r}, {!r}, {!r}]; "
            "import test_bench_mesh_step; test_bench_mesh_step._child()"
            ).format(os.path.dirname(os.path.abspath(__file__)), _paths.ROOT,
                     os.path.join(_paths.ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=_paths.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("CASE "):
            case = json.loads(line[5:])
            out[case.pop("case")] = case
    return out


def test_every_all_reduce_of_the_mesh_step_is_in_the_exchange(cases):
    case = cases["scopes"]
    assert "error" not in case, case
    # dN_wk over the data axis, dN_kd over the model axis and dN_k (the
    # compiler may combine some of them into one)
    assert case["all_reduces"], case
    assert set(case["all_reduces"].values()) == {"zen.exchange"}, case


def test_mesh_draws_use_the_corpus_vocabulary_in_the_smoothing_mass(cases):
    case = cases["smoothing"]
    assert case["padded_words"] >= 1.9 * W, case
    checks = case["checks"]
    assert checks["draw_gap_nats"] <= GAP_LIMIT, case
    assert case["not_argmax_tokens"] == 0, case
    assert checks["count_mismatch"] == 0, case
    assert checks["grid_token_mismatch"] == 0, case
