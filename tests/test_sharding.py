"""Sharding rules: every assigned arch's param specs divide on the
production meshes (subprocess builds a 4-device stand-in + pure spec math
against production mesh shapes)."""
import numpy as np
import pytest

from helpers import run_with_devices


def test_param_specs_divide_on_production_shapes():
    """Validate divisibility of every rule against 16x16 and 2x16x16 by
    constructing the specs on a small mesh with the same axis names and
    checking dims against the production sizes analytically."""
    run_with_devices("""
import warnings; warnings.filterwarnings('ignore')
import jax, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config, list_archs
from repro.launch.specs import params_abstract
from jax.sharding import AbstractMesh
from repro.sharding.partition import param_specs

# the REAL production meshes, as abstract shapes (no 512 devices needed)
MESHES = [
    AbstractMesh((16, 16), ('data', 'model')),
    AbstractMesh((2, 16, 16), ('pod', 'data', 'model')),
]

def axis_size(mesh, entry):
    if entry is None: return 1
    if isinstance(entry, str): return mesh.shape[entry]
    return int(np.prod([mesh.shape[a] for a in entry]))

checked = 0
for mesh in MESHES:
    for arch in list_archs(lm_only=True):
        cfg = get_config(arch)
        shapes = params_abstract(cfg)
        specs = param_specs(shapes, cfg, mesh)
        flat_s = jax.tree_util.tree_leaves_with_path(shapes)
        flat_p = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_s) == len(flat_p)
        for (path, leaf), spec in zip(flat_s, flat_p):
            for dim, entry in zip(leaf.shape, tuple(spec)):
                size = axis_size(mesh, entry)
                assert dim % size == 0, (arch, path, leaf.shape, tuple(spec))
            checked += 1
print('checked', checked, 'leaves')
""", n_devices=4)


def test_sharded_matmul_runs():
    run_with_devices("""
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ('data', 'model'))
x = jax.device_put(jnp.ones((8, 16)), NamedSharding(mesh, P('data', None)))
w = jax.device_put(jnp.ones((16, 8)), NamedSharding(mesh, P(None, 'model')))
y = jax.jit(lambda a, b: a @ b)(x, w)
np.testing.assert_allclose(np.asarray(y), 16.0)
print('OK')
""", n_devices=4)


def test_cache_sharding_rules():
    run_with_devices("""
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.model import init_cache
from repro.sharding import cache_sharding
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ('data', 'model'))
cfg = get_config('qwen3-8b')
# decode_32k-like: batch divides -> batch over data, seq over model
caches = init_cache(cfg, 4, 64, abstract=True)
sh = cache_sharding(caches, mesh)
spec = sh.k.spec
assert spec[1] is not None, spec    # batch sharded
assert spec[2] == 'model', spec     # seq sharded for flash-decode
# long-context batch=1 -> sequence takes every axis
caches1 = init_cache(cfg, 1, 64, abstract=True)
sh1 = cache_sharding(caches1, mesh)
assert sh1.k.spec[2] is not None
print('OK')
""", n_devices=4)


def test_small_scale_sharded_train_step():
    """An actually-executed sharded LM train step on a 2x2 mesh."""
    run_with_devices("""
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.specs import batch_specs, state_abstract
from repro.sharding import batch_sharding, param_shardings
from repro.launch.specs import _opt_shardings
from repro.train.train_step import TrainState, init_train_state, make_train_step
from repro.train.optimizer import OptConfig
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ('data', 'model'))
import dataclasses
cfg = get_config('qwen3-8b-smoke')
cfg = dataclasses.replace(cfg, d_model=128, num_heads=4, num_kv_heads=2,
                          d_ff=256, vocab_size=512)
st = init_train_state(jax.random.key(0), cfg, OptConfig())
p_sh = param_shardings(st.params, cfg, mesh)
opt_sh = _opt_shardings(st.opt_state, st.params, cfg, mesh)
from jax.sharding import NamedSharding, PartitionSpec as P
st_sh = TrainState(params=p_sh, opt_state=opt_sh,
                   step=NamedSharding(mesh, P()))
st = jax.device_put(st, st_sh)
batch = {'tokens': jnp.ones((4, 16), jnp.int32),
         'labels': jnp.ones((4, 16), jnp.int32)}
b_sh = batch_sharding(batch, mesh)
batch = jax.device_put(batch, b_sh)
step = jax.jit(make_train_step(cfg), in_shardings=(st_sh, b_sh),
               out_shardings=(st_sh, None), donate_argnums=(0,))
losses = []
for _ in range(3):
    st, m = step(st, batch)
    losses.append(float(m['loss']))
assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
print('SHARDED TRAIN OK', losses)
""", n_devices=4, timeout=900)
