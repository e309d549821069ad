"""Plain references that decide ``correct``, and their lower-precision
controls. Nothing here imports the system under test.

Training. The sampler draws each token's topic as the argmax of ``log p +
g`` over topics, where ``p`` is the collapsed-Gibbs conditional with the
token itself left out of all three counts,

    p(k) = (N_kd + alpha_k) (N_wk + beta) / (N_k + W beta),

and ``g`` is Gumbel noise from a counter-based hash of (chunk seed, token
row in its chunk, topic). The reference rebuilds the counts from the
assignments it is given, recomputes ``p`` in float32 with the same noise,
and reads for every token the gap by which the drawn topic's score lies
below the best score. The noise stream and its key schedule are copied
here so that the draws can be checked one by one; a program whose draws
come from another stream needs a cell of its own.

On a (rows, cols) mesh each device samples the padded slots of its grid
cell under a key of its own (``mesh_chunk_seeds``). ``grid_draw_gaps``
scores every cell's live slots against counts rebuilt from all cells'
topics, in corpus ids (``unrelabel`` undoes the grid's relabelling), with
the configured W in the smoothing mass, its cells split over the chips;
``grid_token_mismatch`` checks that the live slots hold exactly the
corpus's tokens.

Serving. A request's chain starts from ``randint(key)`` and runs
``num_sweeps`` sweeps against the frozen model, sweep ``j`` drawing with
the per-token seeds hashed from ``split(key)[j]`` and the token's position.
The reference runs the same chain and compares the final doc-topic counts.

The likelihood is the predictive per-token log-likelihood of the counts
(ZenLDA footnote 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLD = 0x9E3779B9
_I32_MAX = 2**31 - 1


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (all of its bits)."""
    key = jax.random.key(seed % 2**32)
    return jax.random.fold_in(key, (seed // 2**32) % 2**32)


# -- noise -------------------------------------------------------------------

def _mix(x):
    x = (x ^ (x >> 16)) * jnp.uint32(_M1)
    x = (x ^ (x >> 13)) * jnp.uint32(_M2)
    return x ^ (x >> 16)


def gumbel(seed, row, col):
    """Gumbel noise of the counter-based hash at integer coordinates."""
    h = _mix(seed.astype(jnp.uint32)
             ^ (row.astype(jnp.uint32) * jnp.uint32(_GOLD))
             ^ _mix(col.astype(jnp.uint32)))
    u = (h >> 9).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 23)) \
        + (0.5 / (1 << 23))
    return -jnp.log(-jnp.log(u))


def chunk_seeds(rng, iteration: int, num_tokens: int, chunk: int):
    """(n_chunks,) int32 seeds of one training sweep's token chunks
    (``iteration`` None: ``rng`` is the sweep's key itself)."""
    key = rng if iteration is None else jax.random.fold_in(rng, iteration)
    if not chunk or chunk >= num_tokens:
        keys = key[None]
    else:
        keys = jax.random.split(key, -(-num_tokens // chunk))
    return jax.vmap(lambda k: jax.random.randint(
        k, (), 0, _I32_MAX, dtype=jnp.int32))(keys)


def mesh_chunk_seeds(rng, iteration: int, dev: int, e_cell: int,
                     chunk: int):
    """(n_chunks,) int32 seeds of one mesh sweep's chunks of device
    ``dev``'s padded cell of ``e_cell`` slots: the device's key is folded
    from the sweep's, and its first half is split over the chunks."""
    key = jax.random.fold_in(jax.random.fold_in(rng, iteration), dev)
    return chunk_seeds(jax.random.split(key)[0], None, e_cell, chunk)


def request_seeds(sweep_key, length: int):
    """(length,) int32 per-token seeds of one serving sweep."""
    bits = jax.random.key_data(sweep_key).astype(jnp.uint32)
    pos = jnp.arange(length, dtype=jnp.uint32)
    h = _mix(bits[0] ^ _mix(bits[1]) ^ (pos * jnp.uint32(_GOLD)))
    return (h & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


# -- counts and the prior ------------------------------------------------------

def counts(word, doc, topic, num_words: int, num_docs: int, k: int):
    """(N_wk, N_kd, N_k) of an assignment, by plain scatter-adds."""
    one = jnp.ones_like(topic)
    n_wk = jnp.zeros((num_words, k), jnp.int32).at[word, topic].add(one)
    n_kd = jnp.zeros((num_docs, k), jnp.int32).at[doc, topic].add(one)
    n_k = jnp.zeros((k,), jnp.int32).at[topic].add(one)
    return n_wk, n_kd, n_k


def hyper(cfg: dict) -> dict:
    """The priors a configuration file states."""
    return {k: cfg[k] for k in ("alpha", "beta", "alpha_prime",
                                "asymmetric_alpha")}


def alpha_k(n_k, hyper: dict):
    """Asymmetric prior alpha_k = K alpha (N_k + alpha'/K) / (N + alpha')."""
    k = float(n_k.shape[0])
    n = n_k.astype(jnp.float32)
    if not hyper.get("asymmetric_alpha", True):
        return jnp.full(n.shape, hyper["alpha"], jnp.float32)
    ap = hyper["alpha_prime"]
    return (k * hyper["alpha"]) * (n + ap / k) / (jnp.sum(n) + ap)


# -- training ------------------------------------------------------------------

def _scores(n_wk, n_kd, n_k, a_k, w, d, z_old, seed, row, beta, w_beta,
            dtype):
    """(B, K) scores log p + g of B tokens, the token left out of its
    counts; ``dtype`` is the precision of the conditional."""
    k = n_k.shape[0]
    cols = jnp.arange(k, dtype=jnp.int32)[None, :]
    own = (cols == z_old[:, None]).astype(dtype)
    nw = n_wk[w].astype(dtype) - own
    nd = n_kd[d].astype(dtype) - own
    nk = n_k.astype(dtype)[None, :] - own
    p = (nd + a_k.astype(dtype)[None, :]) * (nw + jnp.asarray(beta, dtype)) \
        / (nk + jnp.asarray(w_beta, dtype))
    g = gumbel(seed[:, None], row[:, None], cols).astype(dtype)
    return jnp.log(p) + g


def _per_block(fn, block: int, *arrays):
    """``fn(*block_arrays, live)`` over blocks of ``block`` tokens of the
    (T,) ``arrays`` (zero-padded; ``live`` marks real tokens), stacked."""
    t = arrays[0].shape[0]
    n = -(-t // block)
    cols = [jnp.pad(a, (0, n * block - t)).reshape(n, block) for a in arrays]
    live = (jnp.arange(n * block) < t).reshape(n, block)
    return jax.lax.map(lambda xs: fn(*xs), tuple(cols) + (live,))


def _seed_rows(seeds, t: int, chunk: int):
    """Each of ``t`` tokens' chunk seed and row in its chunk."""
    pos = jnp.arange(t, dtype=jnp.int32)
    span = chunk if chunk and chunk < t else t
    return seeds[pos // span], pos % span


def _sweep_inputs(word, doc, z_old, seeds, num_words, num_docs, k, chunk,
                  hyper):
    """Counts of ``z_old``, alpha_k, and each token's chunk seed and row."""
    n_wk, n_kd, n_k = counts(word, doc, z_old, num_words, num_docs, k)
    return ((n_wk, n_kd, n_k, alpha_k(n_k, hyper)),
            *_seed_rows(seeds, word.shape[0], chunk))


def _gap_flips(sc, z_new, ok):
    """(widest gap by which ``z_new``'s score lies below the best, tokens
    whose ``z_new`` is not the best) over the rows where ``ok``."""
    got = jnp.take_along_axis(sc, z_new[:, None], axis=1)[:, 0]
    gap = jnp.where(ok, jnp.max(sc, axis=1) - got, 0.0)
    return jnp.max(gap), jnp.sum((gap > 0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_words", "num_docs", "k",
                                             "chunk", "block", "hyper_t"))
def draw_gaps(word, doc, z_old, z_new, seeds, *, num_words: int,
              num_docs: int, k: int, chunk: int, block: int, hyper_t):
    """Per token of one sweep: the gap (nats) by which ``z_new``'s score
    lies below the reference's best, from the counts of ``z_old``. Returns
    (widest gap, tokens whose draw is not the reference argmax)."""
    hyper = dict(hyper_t)
    model, seed, row = _sweep_inputs(word, doc, z_old, seeds, num_words,
                                     num_docs, k, chunk, hyper)

    def one(w, d, zo, zn, s, r, live):
        return _gap_flips(_scores(*model, w, d, zo, s, r, hyper["beta"],
                                  num_words * hyper["beta"], jnp.float32),
                          zn, live)

    gaps, flips = _per_block(one, block, word, doc, z_old, z_new, seed, row)
    return jnp.max(gaps), jnp.sum(flips)


def grid_sharding(devices):
    """The sharding that splits a (cells, ...) array over ``devices`` by
    cell."""
    mesh = jax.sharding.Mesh(np.asarray(devices), ("chips",))
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        "chips"))


def _grid_counts(word, doc, live, z, num_words, num_docs, k):
    """Counts of the live slots of every chip's cells: each chip counts its
    own, and the sum over chips is on each."""
    one = live.reshape(-1).astype(jnp.int32)
    w, d, t = word.reshape(-1), doc.reshape(-1), z.reshape(-1)
    n_wk = jnp.zeros((num_words, k), jnp.int32).at[w, t].add(one)
    n_kd = jnp.zeros((num_docs, k), jnp.int32).at[d, t].add(one)
    n_k = jnp.zeros((k,), jnp.int32).at[t].add(one)
    return tuple(jax.lax.psum(x, "chips") for x in (n_wk, n_kd, n_k))


def _per_chip(fn, mesh, n_in: int):
    """``fn`` on each chip of ``mesh`` over its cells (axis 0 of each of
    the ``n_in`` arguments), one (cells,) result per output."""
    spec = jax.sharding.PartitionSpec("chips")
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                         out_specs=spec, check_vma=False)


@functools.partial(jax.jit, static_argnames=("mesh", "num_words",
                                             "num_docs", "k", "chunk",
                                             "block", "hyper_t"))
def grid_draw_gaps(word, doc, live, z_old, z_new, seeds, e_cell, *, mesh,
                   num_words: int, num_docs: int, k: int, chunk: int,
                   block: int, hyper_t):
    """``draw_gaps`` over a mesh's grid of (cells, slots) in corpus ids,
    split by cell over the chips of ``mesh``: every cell's live slots are
    scored against the counts of all cells' ``z_old``; padding reads no
    gap. ``seeds`` (cells, chunks) are the cells' chunk seeds
    (``mesh_chunk_seeds``) of the program's ``e_cell`` slots a cell, which
    the arrays may extend with padding. Returns each cell's (widest gap,
    tokens whose draw is not the argmax)."""
    hyper = dict(hyper_t)

    def local(word, doc, live, z_old, z_new, seeds):
        n_wk, n_kd, n_k = _grid_counts(word, doc, live, z_old, num_words,
                                       num_docs, k)
        model = (n_wk, n_kd, n_k, alpha_k(n_k, hyper))

        def one(w, d, zo, zn, s, r, lv, inside):
            return _gap_flips(_scores(*model, w, d, zo, s, r, hyper["beta"],
                                      num_words * hyper["beta"],
                                      jnp.float32), zn, lv & inside)

        def cell(xs):
            w, d, lv, zo, zn, sd = xs
            pos = jnp.arange(w.shape[0], dtype=jnp.int32)
            span = jnp.where(chunk < e_cell, chunk, e_cell) if chunk \
                else e_cell
            gaps, flips = _per_block(one, block, w, d, zo, zn,
                                     sd[pos // span], pos % span, lv)
            return jnp.max(gaps), jnp.sum(flips)

        return jax.lax.map(cell, (word, doc, live, z_old, z_new, seeds))

    return _per_chip(local, mesh, 6)(word, doc, live, z_old, z_new, seeds)


@functools.partial(jax.jit, static_argnames=("mesh", "num_words",
                                             "num_docs", "k", "block",
                                             "hyper_t"))
def grid_llh_sums(word, doc, live, topic, *, mesh, num_words: int,
                  num_docs: int, k: int, block: int, hyper_t):
    """Each cell's ``_llh_sum`` over its live slots, under the counts of
    every cell's ``topic``; split by cell over the chips of ``mesh``."""
    hyper = dict(hyper_t)

    def local(word, doc, live, topic):
        model = _grid_counts(word, doc, live, topic, num_words, num_docs, k)
        return jax.lax.map(lambda xs: _llh_sum(*model, xs[0], xs[1],
                                               num_words, block, hyper,
                                               xs[2]), (word, doc, live))

    return _per_chip(local, mesh, 4)(word, doc, live, topic)


@jax.jit
def _pair_mismatch(d1, w1, d2, w2):
    """Positions at which the sorted (doc, word) pairs of two equally long
    token lists differ."""
    s1 = jax.lax.sort((d1, w1), num_keys=2)
    s2 = jax.lax.sort((d2, w2), num_keys=2)
    return jnp.sum(((s1[0] != s2[0]) | (s1[1] != s2[1])).astype(jnp.int32))


def _inverse(perm: np.ndarray, size: int):
    """(inverse of ``perm`` with -1 where no id maps, ids that do not map
    one to one)."""
    perm = np.asarray(perm, np.int64)
    ok = perm >= 0
    inv = np.full(int(perm.max(initial=0)) + 1, -1, np.int64)
    inv[perm[ok]] = np.arange(size)[ok]
    return inv, size - int(np.unique(perm[ok]).size)


def unrelabel(grid_word, grid_doc, live, word_perm, doc_perm,
              num_words: int, num_docs: int):
    """Corpus ids of a grid's live slots, undoing the relabelling
    ``word_perm``/``doc_perm`` (corpus id -> grid id): (word, doc) with -1
    where a grid id maps back to no corpus id, and the corpus ids that
    the relabelling does not map one to one."""
    inv_w, bad_w = _inverse(word_perm, num_words)
    inv_d, bad_d = _inverse(doc_perm, num_docs)

    def back(inv, ids):
        ids = np.asarray(ids)[live]
        ok = (ids >= 0) & (ids < inv.shape[0])
        return np.where(ok, inv[np.where(ok, ids, 0)], -1)

    return back(inv_w, grid_word), back(inv_d, grid_doc), bad_w + bad_d


def grid_token_mismatch(word, doc, grid_w, grid_d, bad_ids: int) -> int:
    """Tokens by which a grid's live slots, in corpus ids (``unrelabel``),
    differ from the corpus as multisets of (word, doc): ids that map back
    to nothing or not one to one, the difference in token counts, and the
    positions at which the sorted pairs differ. 0 when the grid holds
    exactly the corpus's tokens."""
    unmapped = int(np.sum((grid_w < 0) | (grid_d < 0)))
    n = min(word.shape[0], grid_w.shape[0])
    differ = int(_pair_mismatch(
        jnp.asarray(doc[:n], jnp.int32), jnp.asarray(word[:n], jnp.int32),
        jnp.asarray(grid_d[:n], jnp.int32),
        jnp.asarray(grid_w[:n], jnp.int32)))
    return bad_ids + unmapped + abs(word.shape[0] - grid_w.shape[0]) + differ


@functools.partial(jax.jit, static_argnames=("num_words", "num_docs", "k",
                                             "chunk", "block", "hyper_t",
                                             "dtype"))
def control_draws(word, doc, z_old, seeds, *, num_words: int, num_docs: int,
                  k: int, chunk: int, block: int, hyper_t, dtype):
    """The reference put in the sampler's place, its conditional computed
    in ``dtype``: the topics it draws for one sweep from ``z_old``."""
    hyper = dict(hyper_t)
    model, seed, row = _sweep_inputs(word, doc, z_old, seeds, num_words,
                                     num_docs, k, chunk, hyper)

    def one(w, d, zo, s, r, live):
        sc = _scores(*model, w, d, zo, s, r, hyper["beta"],
                     num_words * hyper["beta"], dtype)
        return jnp.argmax(sc, axis=1).astype(jnp.int32)

    z = _per_block(one, block, word, doc, z_old, seed, row)
    return z.reshape(-1)[:word.shape[0]]


@functools.partial(jax.jit, static_argnames=("num_words", "num_docs", "k",
                                             "block", "hyper_t"))
def nll_per_token(word, doc, topic, *, num_words: int, num_docs: int, k: int,
                  block: int, hyper_t):
    """Negative predictive log-likelihood per token of an assignment:
    -mean_t log sum_k theta_dk phi_kw (ZenLDA footnote 6)."""
    n_wk, n_kd, n_k = counts(word, doc, topic, num_words, num_docs, k)
    return -_llh_sum(n_wk, n_kd, n_k, word, doc, num_words, block,
                     dict(hyper_t)) / word.shape[0]


def _llh_sum(n_wk, n_kd, n_k, word, doc, num_words, block, hyper, *live):
    """Predictive log-likelihood summed over tokens (those where ``live``,
    if given) under the counts (N_wk, N_kd, N_k)."""
    a_k = alpha_k(n_k, hyper)
    n_d = jnp.sum(n_kd, axis=1).astype(jnp.float32)
    beta = hyper["beta"]

    def one(w, d, *ok):
        theta = (n_kd[d].astype(jnp.float32) + a_k[None, :]) / \
            (n_d[d][:, None] + jnp.sum(a_k))
        phi = (n_wk[w].astype(jnp.float32) + beta) / \
            (n_k.astype(jnp.float32)[None, :] + num_words * beta)
        ok = ok[0] if len(ok) == 1 else ok[0] & ok[1]
        return jnp.sum(jnp.where(ok, jnp.log(jnp.sum(theta * phi, 1)), 0.0))

    return jnp.sum(_per_block(one, block, word, doc, *live))


@functools.partial(jax.jit, static_argnames=("num_words", "num_docs", "k"))
def count_mismatch(word, doc, topic, n_wk, n_kd, n_k, *, num_words: int,
                   num_docs: int, k: int):
    """Entries of the given counts that differ from the counts of
    ``topic`` (0 when the count update is exact)."""
    r_wk, r_kd, r_k = counts(word, doc, topic, num_words, num_docs, k)
    return (jnp.sum((r_wk != n_wk).astype(jnp.int32))
            + jnp.sum((r_kd != n_kd).astype(jnp.int32))
            + jnp.sum((r_k != n_k).astype(jnp.int32)))


# -- serving -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_sweeps", "hyper_t",
                                             "dtype"))
def serve_chains(keys, words, mask, n_wk, n_k, *, num_sweeps: int, hyper_t,
                 dtype=jnp.float32):
    """Final doc-topic counts (B, K) of B requests' chains against the
    frozen model; ``words``/``mask`` are (B, L) padded rows, ``keys`` the
    requests' chain keys. ``dtype`` is the precision of the conditional."""
    hyper = dict(hyper_t)
    k = n_k.shape[0]
    a_k = alpha_k(n_k, hyper).astype(dtype)
    beta = hyper["beta"]
    w_beta = n_wk.shape[0] * beta
    length = words.shape[1]
    cols = jnp.arange(k, dtype=jnp.int32)[None, :]

    def chain(key, w, m):
        z0 = jax.random.randint(key, (length,), 0, k, dtype=jnp.int32)
        mi = m.astype(jnp.int32)
        nd0 = jnp.zeros((k,), jnp.int32).at[z0].add(mi)
        nw = n_wk[w].astype(dtype) + jnp.asarray(beta, dtype)
        den = n_k.astype(dtype)[None, :] + jnp.asarray(w_beta, dtype)
        sweep_keys = jax.random.split(key, num_sweeps)

        def sweep(carry, sk):
            z, nd = carry
            own = (cols == z[:, None]).astype(dtype)
            p = (nd.astype(dtype)[None, :] - own + a_k[None, :]) * nw / den
            s = request_seeds(sk, length)
            g = gumbel(s[:, None], jnp.zeros((length, 1), jnp.int32),
                       cols).astype(dtype)
            zn = jnp.argmax(jnp.log(p) + g, axis=1).astype(jnp.int32)
            zn = jnp.where(m, zn, z)
            return (zn, jnp.zeros((k,), jnp.int32).at[zn].add(mi)), None

        (_, nd), _ = jax.lax.scan(sweep, (z0, nd0), sweep_keys)
        return nd

    return jax.vmap(chain)(keys, words, mask)


def theta_counts(theta: np.ndarray, length: int, a_k: np.ndarray) -> np.ndarray:
    """The doc-topic counts a served theta stands for: theta (n + sum
    alpha) - alpha, unrounded, so that any change to theta shows."""
    return theta.astype(np.float64) * (length + a_k.sum()) - a_k
