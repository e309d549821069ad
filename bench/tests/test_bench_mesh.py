"""A training cell on a (2, 2) mesh of four CPU devices, at the fault
test's small size, the look for a chip skipped: ``correct`` must come out
true when nothing is broken, and false for each fault that only a mesh
can have or that its reference has to catch in another frame. The runs
are made in one child process with four host devices (the device count
is fixed at JAX's first start), one JSON line per case."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import _paths

SEED = 2**31 + 97
OVERRIDES = {
    "cfg": {"num_words": 600, "num_topics": 24, "num_docs": 50,
            "token_chunk": 2048, "mesh_shape": [2, 2]},
    "limits": {"grid_token_mismatch": 0},
}
# each fault, and the check that has to catch it
FAULTS = {"cell_unchanged": "draw_gap_nats", "token_altered": "draw_gap_nats",
          "exchange_dropped": "count_mismatch",
          "seed_altered": "draw_gap_nats",
          "slot_duplicated": "grid_token_mismatch"}


def _with_grid_topics(session, state, z):
    """``state`` with grid topics ``z`` and counts recounted from them in
    the grid's relabelled ids, laid out as the step lays them out."""
    import jax

    from bench import reference

    grid, live = session.plan.grid, session.plan.grid.mask
    n_wk, n_kd, n_k = reference.counts(
        grid.word[live], grid.doc[live], z[live], state.n_wk.shape[0],
        state.n_kd.shape[0], state.n_k.shape[0])
    return state._replace(
        topic=jax.device_put(z, state.topic.sharding),
        n_wk=jax.device_put(n_wk, state.n_wk.sharding),
        n_kd=jax.device_put(n_kd.astype(state.n_kd.dtype),
                            state.n_kd.sharding),
        n_k=jax.device_put(n_k, state.n_k.sharding))


def _first_device():
    import jax

    return ((jax.lax.axis_index("data") == 0)
            & (jax.lax.axis_index("model") == 0))


def _plant(patch, fault):
    """Break the mesh path for ``fault`` with ``patch`` (a
    ``pytest.MonkeyPatch``)."""
    import jax
    import jax.numpy as jnp

    from repro.algorithms import zen_pallas
    from repro.core import graph
    from repro.train.session import MeshPlan, TrainSession

    if fault in ("cell_unchanged", "token_altered"):
        orig = TrainSession.step

        def step(self, state):
            old = np.asarray(state.topic)  # the step donates its state
            new = orig(self, state)
            z = np.asarray(new.topic).copy()
            if fault == "cell_unchanged":
                z[0] = old[0]
            else:
                i = int(np.flatnonzero(self.plan.grid.mask[1])[7])
                z[1, i] = (z[1, i] + 1) % self.hyper.num_topics
            return _with_grid_topics(self, new, z)

        patch.setattr(TrainSession, "step", step)
    elif fault == "exchange_dropped":
        init, psum = MeshPlan.init, jax.lax.psum

        def dropping(x, axes):  # the first device's dN_wk never arrives
            if axes == ("data",):
                x = jnp.where(_first_device(), jnp.zeros_like(x), x)
            return psum(x, axes)

        def init_then_drop(self, *a, **kw):
            state = init(self, *a, **kw)  # the init's recount stays whole
            patch.setattr(jax.lax, "psum", dropping)
            return state

        patch.setattr(MeshPlan, "init", init_then_drop)
    elif fault == "seed_altered":
        chunked = zen_pallas.chunked_token_map

        def altered(chunk_fn, key, arrays, token_chunk):
            bits = jnp.where(_first_device(),
                             jax.random.key_data(jax.random.fold_in(key, 1)),
                             jax.random.key_data(key))
            return chunked(chunk_fn, jax.random.wrap_key_data(bits), arrays,
                           token_chunk)

        patch.setattr(zen_pallas, "chunked_token_map", altered)
    elif fault == "slot_duplicated":
        partition = graph.grid_partition

        def duplicating(*a, **kw):
            g = partition(*a, **kw)
            pair = np.stack([g.word[0], g.doc[0]])
            j = int(np.flatnonzero((pair != pair[:, :1]).any(0)
                                   & g.mask[0])[0])
            g.word[0, 0], g.doc[0, 0] = g.word[0, j], g.doc[0, j]
            return g

        patch.setattr(graph, "grid_partition", duplicating)


def _child() -> None:
    """Run the unbroken case and every fault; print one line each."""
    from bench import run

    for fault in (None,) + tuple(FAULTS):
        patch = pytest.MonkeyPatch()
        try:
            _plant(patch, fault)
            result, checks = run.run_cell(
                "train-nytimes", SEED, 1.0, False, require_chip=False,
                overrides={k: dict(v) for k, v in OVERRIDES.items()})
            line = {"correct": result["correct"],
                    "attempted": result["attempted"],
                    "checks": {c.name: [c.value, c.limit] for c in checks}}
        except Exception as e:  # a crash is reported as that case's
            line = {"error": repr(e)}
        finally:
            patch.undo()
        print("CASE " + json.dumps(dict(line, fault=fault)), flush=True)


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import sys; sys.path[:0] = [{!r}, {!r}, {!r}]; "
            "import test_bench_mesh; test_bench_mesh._child()").format(
                os.path.dirname(os.path.abspath(__file__)), _paths.ROOT,
                os.path.join(_paths.ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=_paths.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("CASE "):
            case = json.loads(line[5:])
            out[case.pop("fault")] = case
    return out


def test_mesh_run_is_correct(cases):
    case = cases[None]
    assert case.get("correct") is True, case
    assert case["attempted"] > 0
    assert sorted(case["checks"]) == ["count_mismatch", "draw_gap_nats",
                                      "grid_token_mismatch"]


@pytest.mark.parametrize("fault", FAULTS)
def test_mesh_fault_fails_correct(cases, fault):
    case = cases[fault]
    assert "error" not in case, case
    assert case["correct"] is False, case
    value, limit = case["checks"][FAULTS[fault]]
    assert value > limit, case
