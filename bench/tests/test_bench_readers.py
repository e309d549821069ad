"""The training readers on a trace recorded on a v5e chip: at one chip
each reads what it read before it divided by the cell's chips, on four a
quarter of it; the scope readers sum the ops of their scope."""
import os

import pytest

import _paths
from bench import run, trace, work

DATA = os.path.join(_paths.ROOT, "bench", "tests", "data")
STEPS = 3
PEAKS = work.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def red():
    return trace.reduce(trace.load(os.path.join(DATA, "program.xplane.pb")))


def _ctx(red, chips, scopes=None):
    cfg = run.load_cell("train-nytimes")[2]
    return {"trace": red, "steps": STEPS, "tokens_per_step": 1_000_000,
            "window_s": 1.0, "tokens_per_s": 3_000_000.0, "cfg": cfg,
            "peaks": PEAKS, "chips": chips, "scopes": scopes}


def _read(name, ctx):
    return run.metric_reader(name).read(ctx)


def test_one_chip_reads_as_before(red):
    ctx = _ctx(red, 1)
    # the readers' arithmetic before they divided by the chips
    assert _read("train_kernel_ms", ctx) == red.kernel_s() / STEPS * 1e3
    assert _read("train_xla_ms", ctx) == red.non_kernel_s() / STEPS * 1e3
    assert _read("train_mfu", ctx) == 100.0 * 3_000_000.0 * \
        work.train_step_least_seconds_per_token(1000, PEAKS)
    assert _read("train_kernel_roofline", ctx) == 100.0 * \
        work.train_kernel_least_seconds(1_000_000, 1000, PEAKS) / \
        (red.kernel_s() / STEPS)
    assert _read("train_idle_share", ctx) == 100.0 * red.idle_share


@pytest.mark.parametrize("name", ["train_kernel_ms", "train_xla_ms",
                                  "train_mfu"])
def test_four_chips_read_a_quarter(red, name):
    one = _read(name, _ctx(red, 1))
    assert one > 0
    assert _read(name, _ctx(red, 4)) == pytest.approx(one / 4, rel=1e-12)


@pytest.mark.parametrize("name", ["train_kernel_roofline",
                                  "train_idle_share"])
def test_shares_do_not_depend_on_chips(red, name):
    assert _read(name, _ctx(red, 4)) == _read(name, _ctx(red, 1))


@pytest.mark.parametrize("name,scope", [("train_delta_ms", "zen.delta_counts"),
                                        ("train_relayout_ms", "zen.relayout")])
def test_scope_readers_sum_their_scope(red, name, scope):
    # a hand-made scope map of the recorded program's instructions
    names = sorted({trace.op_name(k) for k in red.op_s})
    scopes = {n: scope if n.startswith("copy") else "zen.sweep"
              for n in names}
    want = sum(v for k, v in red.op_s.items()
               if trace.op_name(k).startswith("copy"))
    assert want > 0
    for chips in (1, 4):
        got = _read(name, _ctx(red, chips, scopes))
        assert got == pytest.approx(want / STEPS * 1e3 / chips, rel=1e-12)
    # the step has no such scope; no scope map; only the kernel wrappers'
    # scope, spread over the whole program (the mesh step): nothing read
    for no in ({n: "zen.sweep" for n in names}, None,
               {n: "zen.relayout" for n in names}):
        assert _read(name, _ctx(red, 1, no)) is None
