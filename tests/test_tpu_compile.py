"""Compile-for-v5e rehearsal of the five Pallas kernels at the paper's
NYTimes widths (K=1,000; W=101,636 for the kernels that read a resident
count matrix), through the ``ops`` wrappers with ``interpret=False``.

Nothing runs: the TPU compiler, installed with jax, compiles for a
described v5e, which catches what interpret mode cannot — misaligned
tiles, unsupported Mosaic ops, VMEM or SMEM overuse. Each compile must
keep its kernel as a ``tpu_custom_call`` under its ``pallas_call`` name.
The topology is described inside a module fixture (never at import), so
only the worker that runs these tests loads the TPU library; the
persistent compile cache is off around the compiles (an entry written for
a described chip cannot be read back here).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

W, K, D = 101_636, 1_000, 4_096
T = 4_096
# the token count of chip_smoke.py's corpus: zen_cdf hands every token of
# the corpus to one cdf_row_search call
T_SMOKE = 1_359_322
BETA = 0.01
i32, f32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _sampler_kw():
    return dict(beta=BETA, w_beta=W * BETA, interpret=False)


# (wrapper call, argument (shape, dtype) list)
CASES = {
    "zen_sample": (
        lambda a, b, z, al, nk, s: ops.zen_sample(
            a, b, z, al, nk, s, **_sampler_kw()),
        [((T, K), i32), ((T, K), i32), ((T,), i32), ((K,), f32),
         ((K,), f32), ((), i32)],
    ),
    "zen_infer_sample": (
        lambda a, b, z, s, al, nk: ops.zen_infer_sample(
            a, b, z, s, al, nk, **_sampler_kw()),
        [((T, K), i32), ((T, K), i32), ((T,), i32), ((T,), i32),
         ((K,), f32), ((K,), f32)],
    ),
    "zen_fused_sample": (
        lambda a, b, w, d, z, al, nk, s: ops.zen_fused_sample(
            a, b, w, d, z, al, nk, s, **_sampler_kw()),
        [((W, K), i32), ((D, K), i32), ((T,), i32), ((T,), i32),
         ((T,), i32), ((K,), f32), ((K,), f32), ((), i32)],
    ),
    "zen_fused_sample_smoke_t": (
        lambda a, b, w, d, z, al, nk, s: ops.zen_fused_sample(
            a, b, w, d, z, al, nk, s, **_sampler_kw()),
        [((W, K), i32), ((D, K), i32), ((T_SMOKE,), i32),
         ((T_SMOKE,), i32), ((T_SMOKE,), i32), ((K,), f32), ((K,), f32),
         ((), i32)],
    ),
    "zen_fused_infer_sample": (
        lambda a, b, w, d, z, s, al, nk: ops.zen_fused_infer_sample(
            a, b, w, d, z, s, al, nk, **_sampler_kw()),
        [((W, K), i32), ((256, K), i32), ((T,), i32), ((T,), i32),
         ((T,), i32), ((T,), i32), ((K,), f32), ((K,), f32)],
    ),
    "cdf_row_search": (
        lambda c, r, t, g: ops.cdf_row_search(c, r, t, g, interpret=False),
        [((W, K), i32), ((T,), i32), ((K,), f32), ((T,), f32)],
    ),
    "cdf_row_search_smoke_t": (
        lambda c, r, t, g: ops.cdf_row_search(c, r, t, g, interpret=False),
        [((W, K), i32), ((T_SMOKE,), i32), ((K,), f32), ((T_SMOKE,), f32)],
    ),
    "sparse_row_sample": (
        lambda v, tp, g: ops.sparse_row_sample(v, tp, g, interpret=False),
        [((T, K), f32), ((T, K), i32), ((T,), f32)],
    ),
    "topic_histogram": (
        lambda r, a, b, c: ops.topic_histogram(
            r, a, b, c, D, K, interpret=False),
        [((T,), i32), ((T,), i32), ((T,), i32), ((T,), i32)],
    ),
}


# each case's pallas_call name, which its op_name carries
KERNEL_NAMES = {
    "zen_sample": "zen_sample", "zen_infer_sample": "zen_infer_sample",
    "zen_fused_sample": "zen_fused_sample",
    "zen_fused_sample_smoke_t": "zen_fused_sample",
    "zen_fused_infer_sample": "zen_fused_infer_sample",
    "cdf_row_search": "cdf_search", "cdf_row_search_smoke_t": "cdf_search",
    "sparse_row_sample": "sparse_row", "topic_histogram": "topic_histogram",
}


def _compile(name, one_chip):
    fn, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in arg_specs]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    compiled = _compile(name, one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" in text, name
    assert f"/{KERNEL_NAMES[name]}/pallas_call" in text, name
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16 * 2**30, (name, used)


@pytest.mark.parametrize("name", ["zen_fused_sample",
                                  "zen_fused_infer_sample"])
def test_fused_relayout_keeps_its_scope_on_v5e(name, one_chip):
    """The count matrices' pad and row view before a fused kernel keep the
    named scope ``zen.relayout`` through the v5e compiler."""
    lines = _compile(name, one_chip).as_text().splitlines()
    scoped = [ln for ln in lines if "zen.relayout" in ln]
    assert any(" pad(" in ln for ln in scoped), name
    assert any(" reshape(" in ln for ln in scoped), name
