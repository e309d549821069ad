"""``input_specs``: ShapeDtypeStruct stand-ins + shardings for every cell.

No device allocation anywhere — weak-type-correct abstract values only.
Each (arch x shape) cell resolves to:

  step_kind 'train'    -> train_step(state, batch)
  step_kind 'prefill'  -> prefill_step(params, batch)   (forward, logits)
  step_kind 'decode'   -> serve_step(params, token, caches)
  step_kind 'lda'      -> lda_step(state, data)         (one CGS iteration)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import ArchConfig, LDAArchConfig, ShapeConfig
from repro.models.model import init_cache
from repro.sharding import (
    batch_sharding,
    cache_sharding,
    data_axes_of,
    param_shardings,
)
from repro.train.optimizer import OptConfig
from repro.train.train_step import init_train_state


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Abstract batch for a full-sequence (train/prefill) cell."""
    b, s = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    batch: Dict[str, Any] = {}
    if cfg.family == "encdec":
        # stub audio frontend: precomputed frame embeddings
        batch["enc_embeds"] = _sds((b, s, cfg.d_model), dt)
        batch["tokens"] = _sds((b, s), jnp.int32)
    elif cfg.family == "vlm":
        # stub vision frontend: patch embeddings + 3D M-RoPE position ids
        batch["embeds"] = _sds((b, s, cfg.d_model), dt)
        batch["positions"] = _sds((b, s, 3), jnp.int32)
    else:
        batch["tokens"] = _sds((b, s), jnp.int32)
    if shape.kind == "train":
        batch["labels"] = _sds((b, s), jnp.int32)
    return batch


def params_abstract(cfg: ArchConfig) -> Any:
    from repro.models.model import init_params

    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))


def state_abstract(cfg: ArchConfig) -> Any:
    return jax.eval_shape(
        lambda k: init_train_state(k, cfg, OptConfig()), jax.random.key(0)
    )


def lm_cell_specs(
    cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh
) -> Tuple[str, Dict[str, Any], Dict[str, Any]]:
    """(step_kind, kwargs of ShapeDtypeStructs, kwargs of shardings)."""
    if shape.kind == "train":
        state = state_abstract(cfg)
        batch = batch_specs(cfg, shape)
        # params + opt state share the param rules; step scalar replicated
        p_sh = param_shardings(state.params, cfg, mesh)
        opt_sh = _opt_shardings(state.opt_state, state.params, cfg, mesh)
        from repro.train.train_step import TrainState

        st_sh = TrainState(
            params=p_sh, opt_state=opt_sh, step=NamedSharding(mesh, P())
        )
        return (
            "train",
            {"state": state, "batch": batch},
            {"state": st_sh, "batch": batch_sharding(batch, mesh)},
        )
    if shape.kind == "prefill":
        params = params_abstract(cfg)
        batch = batch_specs(cfg, shape)
        return (
            "prefill",
            {"params": params, "batch": batch},
            {
                "params": param_shardings(params, cfg, mesh),
                "batch": batch_sharding(batch, mesh),
            },
        )
    # decode
    params = params_abstract(cfg)
    b = shape.global_batch
    s_enc = shape.seq_len if cfg.family == "encdec" else 0
    caches = init_cache(cfg, b, shape.seq_len, s_enc=s_enc, abstract=True)
    token = _sds((b,), jnp.int32)
    dp = int(np.prod([mesh.shape[a] for a in data_axes_of(mesh)]))
    tok_sh = NamedSharding(
        mesh, P(data_axes_of(mesh)) if b % dp == 0 else P()
    )
    return (
        "decode",
        {"params": params, "token": token, "caches": caches},
        {
            "params": param_shardings(params, cfg, mesh),
            "token": tok_sh,
            "caches": cache_sharding(caches, mesh),
        },
    )


def _opt_shardings(opt_state, params, cfg, mesh):
    """Optimizer-state shardings: moments follow their param's rule; factored
    stats inherit the param rule with the reduced dim dropped; scalars
    replicate."""
    from repro.sharding.partition import param_specs
    from repro.train.optimizer import AdamWState, AdafactorState, FactoredStat

    p_specs = param_specs(params, cfg, mesh)
    if isinstance(opt_state, AdamWState):
        msh = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), p_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        return AdamWState(
            step=NamedSharding(mesh, P()), m=msh,
            v=jax.tree.map(
                lambda spec: NamedSharding(mesh, spec), p_specs,
                is_leaf=lambda x: isinstance(x, P),
            ),
        )
    assert isinstance(opt_state, AdafactorState)

    def stat_sh(spec, stat):
        if isinstance(stat, FactoredStat):
            row_spec = P(*spec[:-1]) if len(spec) else P()
            col_spec = P(*(tuple(spec[:-2]) + (spec[-1],))) if len(spec) >= 2 else P()
            return FactoredStat(
                row=NamedSharding(mesh, row_spec),
                col=NamedSharding(mesh, col_spec),
            )
        return NamedSharding(mesh, spec)

    stats = jax.tree.map(
        stat_sh, p_specs, opt_state.stats,
        is_leaf=lambda x: isinstance(x, P),
    )
    return AdafactorState(step=NamedSharding(mesh, P()), stats=stats)


# ---------------------------------------------------------------------------
# LDA cells
# ---------------------------------------------------------------------------

def lda_cell_specs(
    cfg: LDAArchConfig, mesh: Mesh
) -> Tuple[str, Dict[str, Any], Dict[str, Any], Dict[str, int]]:
    """Abstract DistLDAState/DistLDAData for one streaming iteration."""
    from repro.core.distributed import DistLDAData, DistLDAState, state_shardings

    data_axes = data_axes_of(mesh)
    dp = int(np.prod([mesh.shape[a] for a in data_axes]))
    mp = mesh.shape["model"]
    cells = dp * mp
    k = cfg.num_topics
    e_cell = int(np.ceil(cfg.tokens_per_step / cells / 8) * 8)
    wps = int(np.ceil(cfg.num_words / mp / 8) * 8)
    dps = int(np.ceil(cfg.docs_per_step / dp / 8) * 8)
    tok = _sds((cells, e_cell), jnp.int32)
    state = DistLDAState(
        topic=tok, prev_topic=tok,
        n_wk=_sds((wps * mp, k), jnp.int32),
        n_kd=_sds((dps * dp, k), jnp.dtype(getattr(cfg, "kd_dtype", "int32"))),
        n_k=_sds((k,), jnp.int32),
        stale_iters=tok, same_count=tok,
        iteration=_sds((), jnp.int32),
        rng=jax.eval_shape(lambda: jax.random.key(0)),
    )
    data = DistLDAData(
        word=tok, doc=tok, mask=_sds((cells, e_cell), jnp.bool_)
    )
    st_sh, dt_sh = state_shardings(mesh)
    dims = {"words_per_shard": wps, "docs_per_shard": dps, "e_cell": e_cell,
            "num_words": cfg.num_words}
    return "lda", {"state": state, "data": data}, {
        "state": st_sh, "data": dt_sh,
    }, dims
