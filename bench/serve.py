"""Serving cells: ``LDAEngine`` on a frozen model drawn from the seed.

The frozen model is the generator's ground truth at the published corpus
size (``generator.ground_truth_counts``). Requests are documents drawn from
the same model. ``arrival`` in the traffic file picks the load:

* ``open``: requests due at seeded Poisson times at ``rate`` per second,
  submitted on schedule whatever the server does; latency runs from the
  due time to the engine's completion stamp;
* ``closed``: ``outstanding`` requests kept in flight, topped up as any
  completes; the loop runs from set-up's end until the first request has
  finished, so the window opens on a full pipeline.

The engine runs with its own background ticker (``engine.start()``), as
``launch/serve_lda.py`` runs it. After the window every request left is
awaited (a minute at most); one that never finishes is failed. Then the
engine is freed and the reference re-runs a seeded sample of the finished
requests' chains (``reference.serve_chains``).
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import generator, reference
from bench.harness import Check, Span, device_peak_bytes, percentile

GRACE_S = 60.0  # how long after the window a late request is awaited


def frozen_model(seed: int, cfg: dict):
    """(N_wk, N_k, model) of the published corpus's size, on the device."""
    import jax

    key = reference.seed_key(seed)
    k_model, k_counts = jax.random.split(jax.random.fold_in(key, 1))
    model = generator.make_model(k_model, cfg)
    total = int(cfg["published_num_docs"] * cfg["avg_doc_len"])
    n_wk, n_k = generator.ground_truth_counts(
        k_counts, model.q, model.home, num_topics=int(cfg["num_topics"]),
        total_tokens=total)
    return n_wk, n_k, model


def request_docs(seed: int, cfg: dict, traffic: dict, model, n: int):
    """``n`` documents (host int32 arrays) with the traffic's length mix."""
    import jax

    key = jax.random.fold_in(reference.seed_key(seed), 2)
    k_perm, k_tok = jax.random.split(key)
    lengths = generator.length_multiset(
        n, float(cfg["avg_doc_len"]), traffic["length_sigma"],
        traffic["length_min"], traffic["length_max"])
    lengths = lengths[np.asarray(jax.random.permutation(k_perm, n))]
    word, _ = generator._draw_tokens(
        k_tok, model.q, model.home, model.pi,
        jax.numpy.asarray(lengths, jax.numpy.int32),
        num_topics=int(cfg["num_topics"]), doc_topics=traffic["doc_topics"],
        total=int(lengths.sum()))
    word = np.asarray(word)
    return np.split(word, np.cumsum(lengths)[:-1])


def arrival_offsets(seed: int, n: int, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of ``n`` Poisson arrivals:
    the fixed gap multiset, seeded order, scaled to end inside the window."""
    import jax

    gaps = generator.gap_multiset(n, n / seconds)
    perm = np.asarray(jax.random.permutation(
        jax.random.fold_in(reference.seed_key(seed), 3), n))
    gaps = gaps[perm]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds * (1.0 - 0.5 / n) / np.sum(gaps))


class Load:
    """Submits requests in order, and after the window collects what each
    needs.

    The engine's ticker holds its lock for a whole tick, so a submission
    gets in only between ticks. A submission in the window is therefore
    one ``submit_async`` and nothing else: the request's key is made
    ahead of it (``make_keys``), and completions are counted from the
    engine's ``docs_done`` counter, which needs no lock."""

    def __init__(self, engine, docs, key_data):
        import jax

        self.engine, self.docs, self.key_data = engine, docs, key_data
        self._wrap = jax.random.wrap_key_data
        self.sent = []  # (request index, ticket, due, submitted)
        self.unsent = 0  # due in the window but never submitted
        self.done0 = engine.docs_done
        self.keys = []  # device keys of the next requests, made ahead
        self.reqs = []  # (request index, InferRequest, due, submitted)

    def make_keys(self, ahead: int) -> None:
        """Make the keys of the next ``ahead`` requests not yet sent."""
        upto = min(len(self.sent) + ahead, len(self.key_data))
        while len(self.keys) < upto:
            self.keys.append(self._wrap(self.key_data[len(self.keys)]))

    def submit(self, due: float) -> None:
        """Submit the next request."""
        i = len(self.sent)
        self.make_keys(1)
        ticket = self.engine.submit_async(self.docs[i % len(self.docs)],
                                          key=self.keys[i])
        self.sent.append((i, ticket, due, time.monotonic()))

    def finished(self) -> int:
        return self.engine.docs_done - self.done0

    def in_flight(self) -> int:
        return len(self.sent) - self.finished()

    def await_all(self, deadline: float) -> None:
        """Wait for every submitted request until ``deadline``."""
        while self.in_flight() > 0 and time.monotonic() < deadline:
            time.sleep(0.002)

    def collect(self) -> None:
        """The engine's record of every submitted request; call it once
        the ticker has stopped."""
        self.reqs = [(i, self.engine.request(t), due, sub)
                     for i, t, due, sub in self.sent]


def run_open(load: Load, offsets, seconds: float) -> float:
    """Open loop: each request is submitted at its due time, until the
    window closes; a request still unsent then is never sent (failed).
    Returns the window's start on the monotonic clock."""
    t0 = time.monotonic()
    end = t0 + seconds
    for i, off in enumerate(offsets):
        due = t0 + off
        now = time.monotonic()
        if now >= end:
            load.unsent = len(offsets) - i
            break
        if due > now:
            time.sleep(due - now)
        with Span("bench.submit"):
            load.submit(due)
    left = end - time.monotonic()
    if left > 0:
        time.sleep(left)
    return t0


class Closed:
    """Closed loop: tops the requests in flight up to ``outstanding``
    whenever any has finished, polling every ``POLL_S``, and makes the
    keys of the next ``outstanding`` requests while it waits."""

    POLL_S = 0.002

    def __init__(self, load: Load, outstanding: int, max_requests: int):
        self.load, self.outstanding = load, outstanding
        self.max_requests = max_requests
        self.low = None  # fewest in flight seen in the window, before a top-up

    def top_up(self) -> int:
        """Submit until ``outstanding`` are in flight; returns how many
        were in flight before."""
        load = self.load
        before = load.in_flight()
        add = min(self.outstanding - before,
                  self.max_requests - len(load.sent))
        if add > 0:
            with Span("bench.submit"):
                for _ in range(add):
                    load.submit(time.monotonic())
        load.make_keys(self.outstanding)
        return before

    def ramp(self, timeout: float) -> None:
        """Run until the first request finishes, so that the window opens
        on a full pipeline (set-up)."""
        deadline = time.monotonic() + timeout
        while self.load.finished() == 0 and time.monotonic() < deadline:
            self.top_up()
            time.sleep(self.POLL_S)

    def run(self, seconds: float) -> float:
        """Keep the loop closed for ``seconds``; returns the window's
        start on the monotonic clock."""
        t0 = time.monotonic()
        end = t0 + seconds
        while time.monotonic() < end and len(self.load.sent) < \
                self.max_requests:
            before = self.top_up()
            self.low = before if self.low is None else min(self.low, before)
            time.sleep(self.POLL_S)
        left = end - time.monotonic()
        if left > 0:
            time.sleep(left)
        return t0


def run(ctx) -> dict:
    import jax

    from repro.core.types import LDAHyperParams
    from repro.serving.lda_engine import FrozenLDAModel, LDAEngine, \
        LDAServeConfig

    cfg, traffic, seed = ctx.cfg, ctx.traffic, ctx.seed
    k = int(cfg["num_topics"])
    hyper = reference.hyper(cfg)
    arrival = traffic["arrival"]
    if arrival == "open":
        n_req = int(round(traffic["rate"] * ctx.seconds))
    else:
        n_req = int(traffic["max_requests"])
    with Span("bench.generate", ctx.setup_split, "generate_s"):
        n_wk, n_k, model = frozen_model(seed, cfg)
        docs = request_docs(seed, cfg, traffic, model,
                            min(n_req, int(traffic["pool"])))
        key_data = np.asarray(jax.random.key_data(jax.random.split(
            jax.random.fold_in(reference.seed_key(seed), 4), n_req)))
        offsets = (arrival_offsets(seed, n_req, ctx.seconds)
                   if arrival == "open" else None)
        jax.block_until_ready(n_wk)
    ctx.log(requests={"planned": n_req, "pool": len(docs),
                      "len_mean": float(np.mean([len(d) for d in docs])),
                      "len_max": int(max(len(d) for d in docs))})
    eng = traffic["engine"]
    with Span("bench.engine", ctx.setup_split, "engine_s"):
        engine = LDAEngine(
            FrozenLDAModel(
                n_wk=n_wk, n_k=n_k,
                hyper=LDAHyperParams(num_topics=k, alpha=hyper["alpha"],
                                     beta=hyper["beta"],
                                     alpha_prime=hyper["alpha_prime"],
                                     asymmetric_alpha=hyper["asymmetric_alpha"])),
            LDAServeConfig(buckets=tuple(eng["buckets"]),
                           max_batch=eng["max_batch"],
                           num_sweeps=eng["num_sweeps"],
                           algorithm=cfg["algorithm"], mode=eng["mode"]),
            seed=seed % 2**31)
    with Span("bench.warm", ctx.setup_split, "compile_s"):
        engine.warm()
    load = Load(engine, docs, key_data)
    with Span("bench.fill", ctx.setup_split, "fill_s"):
        if arrival == "open":
            load.make_keys(n_req)
        else:  # filled before the ticker starts, so nothing waits on it
            closed = Closed(load, traffic["outstanding"], n_req)
            closed.top_up()

    engine.start()
    try:
        if arrival == "closed":
            with Span("bench.ramp", ctx.setup_split, "ramp_s"):
                closed.ramp(GRACE_S)
        with ctx.window() as win:
            if arrival == "open":
                t0 = run_open(load, offsets, ctx.seconds)
            else:
                t0 = closed.run(ctx.seconds)
        with Span("bench.drain"):
            load.await_all(time.monotonic() + GRACE_S)
    finally:
        engine.stop()
    load.collect()
    t_end = t0 + ctx.seconds
    peak = device_peak_bytes()

    t_gave_up = time.monotonic()
    done = [(i, r, due, sub) for i, r, due, sub in load.reqs if r.done]
    failed = len(load.reqs) + load.unsent - len(done)
    # a request that never finished waited until the benchmark gave up
    lat_ms = [((r.t_done if r.done else t_gave_up) - due) * 1e3
              for _, r, due, _ in load.reqs]
    if load.unsent:
        lat_ms += [(t_gave_up - t0 - off) * 1e3
                   for off in offsets[len(load.reqs):]]
    late_ms = [(sub - due) * 1e3 for _, _, due, sub in load.reqs]
    in_window = sum(1 for _, r, _, _ in done if t0 <= r.t_done <= t_end)
    e2e = {}
    if arrival == "open":
        e2e["serve_p95_ms"] = percentile(lat_ms, 95.0)
    else:
        e2e["serve_docs_per_s"] = in_window / ctx.seconds
    layer_ctx = {
        "docs_done": in_window, "late_ms": late_ms,
        "ticks_waited": [r.ticks_waited for _, r, _, _ in load.reqs],
    }
    if arrival == "closed":
        ctx.log(closed_loop={"outstanding": traffic["outstanding"],
                             "in_flight_min": closed.low,
                             "submitted": len(load.sent)})
    # the reference runs on the benchmark's own documents, not the
    # engine's copies of them
    thetas = [(i, docs[i % len(docs)], np.asarray(r.theta))
              for i, r, _, _ in done]
    attempted = len(load.reqs) + load.unsent
    del engine, load

    # -- the reference, after the window -----------------------------------
    share = check_sample(thetas, key_data, n_wk, n_k, hyper,
                         eng["num_sweeps"], seed, traffic["checked_requests"])
    ctx.log(reference={"mismatch_share": share, "requests": len(thetas)})
    checks = [Check("served_mismatch_share", share,
                    ctx.limits["served_mismatch_share"])]
    return {
        "attempted": attempted, "failed": failed,
        "checks": checks, "memory_peak_bytes": peak, "window_s": win.seconds,
        "e2e": e2e, "layer_ctx": layer_ctx,
    }


def check_sample(thetas, key_data, n_wk, n_k, hyper, num_sweeps, seed,
                 n_check, dtype=None):
    """Share of served tokens whose final topic count differs from the
    reference chain's, over a seeded sample of ``n_check`` finished
    requests that always holds the longest one."""
    import jax
    import jax.numpy as jnp

    if not thetas:
        return math.inf
    rng = np.random.default_rng(seed % 2**63)
    longest = max(range(len(thetas)), key=lambda j: len(thetas[j][1]))
    pick = rng.choice(len(thetas), size=min(n_check, len(thetas)),
                      replace=False)
    pick = sorted(set(pick.tolist()) | {longest})
    a_k = np.asarray(reference.alpha_k(n_k, hyper), np.float64)
    hyper_t = tuple(sorted(hyper.items()))
    diff = 0.0
    tokens = 0
    block = 64
    for b in range(0, len(pick), block):
        sel = [thetas[j] for j in pick[b:b + block]]
        width = 1 << max(4, int(math.ceil(math.log2(max(len(s[1])
                                                        for s in sel)))))
        words = np.zeros((len(sel), width), np.int32)
        mask = np.zeros((len(sel), width), bool)
        for r, (_, w, _) in enumerate(sel):
            words[r, :len(w)] = w
            mask[r, :len(w)] = True
        keys = jax.random.wrap_key_data(
            jnp.asarray(key_data[[s[0] for s in sel]]))
        kw = {} if dtype is None else {"dtype": dtype}
        n_ref = np.asarray(reference.serve_chains(
            keys, jnp.asarray(words), jnp.asarray(mask), n_wk, n_k,
            num_sweeps=num_sweeps, hyper_t=hyper_t, **kw))
        for r, (_, w, theta) in enumerate(sel):
            got = reference.theta_counts(theta, len(w), a_k)
            diff += float(np.abs(got - n_ref[r]).sum())
            tokens += len(w)
    return diff / (2.0 * tokens)
