"""Pure-jnp oracles for the Pallas kernels (bit-exact where stated)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.tiles import gather_bk, prefix_sum
from repro.kernels.zen_sampler import gumbel_noise


def zen_sample_ref(
    nwk_rows: jax.Array,
    nkd_rows: jax.Array,
    z_old: jax.Array,
    alpha_k: jax.Array,
    n_k: jax.Array,
    seed: jax.Array,
    *,
    beta: float,
    w_beta: float,
) -> jax.Array:
    """Bit-exact oracle of ``zen_sample_pallas`` (same hash, same math)."""
    t, k = nwk_rows.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, k), 1)
    self_hit = (cols == z_old[:, None]).astype(jnp.float32)
    nw = nwk_rows.astype(jnp.float32) - self_hit
    nd = nkd_rows.astype(jnp.float32) - self_hit
    nk = n_k.astype(jnp.float32)[None, :] - self_hit
    a = alpha_k.astype(jnp.float32)[None, :]
    p = (a * beta + nw * a + nd * (nw + beta)) / (nk + w_beta)
    g = gumbel_noise(jnp.asarray(seed, jnp.int32), rows, cols)
    score = jnp.log(jnp.maximum(p, 1e-30)) + g
    return jnp.argmax(score, axis=-1).astype(jnp.int32)


def zen_probs_ref(
    nwk_rows, nkd_rows, z_old, alpha_k, n_k, *, beta: float, w_beta: float
) -> jax.Array:
    """The exact ¬dw conditional the sampler draws from (for statistical
    tests: chi-square of empirical sampling frequencies)."""
    t, k = nwk_rows.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, k), 1)
    self_hit = (cols == z_old[:, None]).astype(jnp.float32)
    nw = nwk_rows.astype(jnp.float32) - self_hit
    nd = nkd_rows.astype(jnp.float32) - self_hit
    nk = n_k.astype(jnp.float32)[None, :] - self_hit
    a = alpha_k.astype(jnp.float32)[None, :]
    p = (a * beta + nw * a + nd * (nw + beta)) / (nk + w_beta)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def zen_infer_sample_ref(
    nwk_rows: jax.Array,
    nkd_rows: jax.Array,
    z_old: jax.Array,
    seeds: jax.Array,
    alpha_k: jax.Array,
    n_k: jax.Array,
    *,
    beta: float,
    w_beta: float,
) -> jax.Array:
    """Bit-exact oracle of ``zen_infer_sample_pallas`` (frozen-model
    serving variant): doc-side-only exclusion, frozen word/topic totals,
    per-token seeds with (seed, topic) noise coordinates."""
    t, k = nwk_rows.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, k), 1)
    self_hit = (cols == z_old[:, None]).astype(jnp.float32)
    nw = nwk_rows.astype(jnp.float32)
    nd = nkd_rows.astype(jnp.float32) - self_hit
    a = alpha_k.astype(jnp.float32)[None, :]
    p = (nd + a) * (nw + beta) / (n_k.astype(jnp.float32)[None, :] + w_beta)
    g = gumbel_noise(
        seeds.astype(jnp.int32)[:, None], jnp.zeros((t, 1), jnp.uint32), cols
    )
    score = jnp.log(jnp.maximum(p, 1e-30)) + g
    return jnp.argmax(score, axis=-1).astype(jnp.int32)


def zen_fused_sample_ref(
    n_wk: jax.Array,
    n_kd: jax.Array,
    word: jax.Array,
    doc: jax.Array,
    z_old: jax.Array,
    alpha_k: jax.Array,
    n_k: jax.Array,
    seed: jax.Array,
    *,
    beta: float,
    w_beta: float,
) -> jax.Array:
    """Bit-exact oracle of ``ops.zen_fused_sample``: gather the rows, then
    run the v1 oracle — the fused kernel's whole claim is that skipping the
    materialized gather changes nothing."""
    return zen_sample_ref(
        n_wk[word], n_kd[doc], z_old, alpha_k, n_k, seed,
        beta=beta, w_beta=w_beta,
    )


def zen_fused_infer_sample_ref(
    n_wk: jax.Array,
    n_kd: jax.Array,
    word: jax.Array,
    slot: jax.Array,
    z_old: jax.Array,
    seeds: jax.Array,
    alpha_k: jax.Array,
    n_k: jax.Array,
    *,
    beta: float,
    w_beta: float,
) -> jax.Array:
    """Bit-exact oracle of ``ops.zen_fused_infer_sample`` (gather + v1
    serving oracle)."""
    return zen_infer_sample_ref(
        n_wk[word], n_kd[slot], z_old, seeds, alpha_k, n_k,
        beta=beta, w_beta=w_beta,
    )


def cdf_row_search_ref(
    counts: jax.Array,
    rows: jax.Array,
    term: jax.Array,
    targets: jax.Array,
    *,
    bk: int = 512,
) -> jax.Array:
    """Tile-accurate oracle of ``ops.cdf_row_search``: same K-tile walk,
    same ``prefix_sum`` scan, same carry adds — so float round-off matches
    the kernel bit for bit at the same ``bk`` (rounded up to the kernel's
    1024-lane gather tile, as the kernel does). (A whole-row
    ``searchsorted`` would be the *mathematical* spec but could disagree
    on round-off at tile boundaries; the tiled walk IS the kernel's
    contract.)"""
    t = rows.shape[0]
    k = counts.shape[1]
    bk = gather_bk(bk)
    pad = (-k) % bk
    vals = counts[rows].astype(jnp.float32) * term.astype(jnp.float32)[None, :]
    if pad:
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
    tgt = targets.astype(jnp.float32)[:, None]
    acc = jnp.zeros((t,), jnp.float32)
    cnt = jnp.zeros((t,), jnp.int32)
    for j in range(0, k + pad, bk):
        local = prefix_sum(vals[:, j:j + bk])
        cdf = acc[:, None] + local
        cnt = cnt + jnp.sum((cdf < tgt).astype(jnp.int32), axis=1)
        acc = acc + local[:, -1]
    return jnp.minimum(cnt, k - 1)


def sparse_row_sample_ref(
    vals: jax.Array,
    topics: jax.Array,
    targets: jax.Array,
) -> jax.Array:
    """Bit-exact oracle of ``ops.sparse_row_sample``, and the XLA path of
    every padded-sparse backend's row inversion. Lane padding in the
    wrapper is provably inert (lanes appended on the right leave every
    real ``prefix_sum`` lane bitwise unchanged and the clamp stops at the
    last real lane), so the oracle needs no padding replication."""
    j = vals.shape[1]
    vals_f = vals.astype(jnp.float32)
    cdf = prefix_sum(vals_f)
    tgt = targets.astype(jnp.float32)[:, None]
    cnt = jnp.sum((cdf < tgt).astype(jnp.int32), axis=1)
    pos = jnp.minimum(cnt, j - 1)
    return jnp.take_along_axis(
        topics.astype(jnp.int32), pos[:, None], axis=1
    )[:, 0]


def topic_histogram_ref(
    rows: jax.Array,
    z_old: jax.Array,
    z_new: jax.Array,
    inc: jax.Array,
    num_rows: int,
    num_topics: int,
) -> jax.Array:
    """Naive scatter-add oracle of ``topic_histogram_pallas``."""
    out = jnp.zeros((num_rows, num_topics), jnp.int32)
    out = out.at[rows, z_new].add(inc)
    out = out.at[rows, z_old].add(-inc)
    return out
