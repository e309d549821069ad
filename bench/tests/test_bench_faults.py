"""A whole benchmark run at a small size on the CPU, the look for a chip
skipped, with the timed path broken underneath: ``correct`` must come out
false for each fault a cell can have, and true when nothing is broken.
(One chip: no cell has an exchange between chips to leave out.)"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
from bench import reference, run

SMALL = {
    "train": {"cfg": {"num_words": 600, "num_topics": 24, "num_docs": 50,
                      "token_chunk": 2048}},
    "serve": {"cfg": {"num_words": 600, "num_topics": 24,
                      "published_num_docs": 200},
              "traffic": {"rate": 30.0, "max_requests": 600, "pool": 256,
                          "length_max": 120,
                          "engine": {"mode": "throughput",
                                     "buckets": [32, 64, 128],
                                     "max_batch": 8, "num_sweeps": 4}}},
}


def _recount(session, z):
    c = session.corpus
    return reference.counts(c.word, c.doc, z, c.num_words, c.num_docs,
                            session.hyper.num_topics)


def _with_topics(session, state, z):
    n_wk, n_kd, n_k = _recount(session, z)
    return dataclasses.replace(state, topic=z, n_wk=n_wk, n_kd=n_kd,
                               n_k=n_k)


def _train_fault(monkeypatch, fault):
    from repro.train.session import TrainSession

    orig = TrainSession.step

    def step(self, state):
        new = orig(self, state)
        t = state.topic.shape[0]
        if fault == "unchanged":
            return dataclasses.replace(new, topic=state.topic,
                                       n_wk=state.n_wk, n_kd=state.n_kd,
                                       n_k=state.n_k)
        if fault == "half":
            z = jnp.where(jnp.arange(t) < t // 2, new.topic, state.topic)
            return _with_topics(self, new, z)
        if fault == "altered":
            z = new.topic.at[t // 3].set(
                (new.topic[t // 3] + 1) % self.hyper.num_topics)
            return _with_topics(self, new, z)
        if fault == "dropped":  # one token's count update lost
            i = t // 3
            w, d, zi = self.corpus.word[i], self.corpus.doc[i], new.topic[i]
            return dataclasses.replace(
                new, n_wk=new.n_wk.at[w, zi].add(-1),
                n_kd=new.n_kd.at[d, zi].add(-1), n_k=new.n_k.at[zi].add(-1))
        return new

    monkeypatch.setattr(TrainSession, "step", step)


def _serve_fault(monkeypatch, fault):
    from repro.algorithms.zen_pallas import ZenPallas
    from repro.serving.lda_engine import LDAEngine

    orig = ZenPallas.infer_sweep

    def infer_sweep(self, keys, words, mask, z_old, *a, **kw):
        z = orig(self, keys, words, mask, z_old, *a, **kw)
        if fault == "unchanged":
            return z_old
        if fault == "half":
            odd = (jnp.arange(z.shape[0]) % 2 == 1)[:, None]
            return jnp.where(odd, z_old, z)
        return z

    monkeypatch.setattr(ZenPallas, "infer_sweep", infer_sweep)
    if fault == "dropped":  # the engine's copy of a document loses a token
        submit = LDAEngine._submit

        def _submit(self, words, *a, **kw):
            req = submit(self, words, *a, **kw)
            if req.uid % 5 == 0 and req.words.shape[0] > 1:
                req.words = req.words[:-1]
            return req

        monkeypatch.setattr(LDAEngine, "_submit", _submit)
    if fault == "altered":
        theta_of = LDAEngine._theta

        def theta(self, req, n_kd_row, alpha_k):
            out = theta_of(self, req, n_kd_row, alpha_k)
            return np.roll(out, 1) if req.uid % 5 == 0 else out

        monkeypatch.setattr(LDAEngine, "_theta", theta)


CASES = ([("train-nytimes", f)
          for f in (None, "unchanged", "half", "altered", "dropped")]
         + [(cell, f) for cell in ("serve-nytimes-steady",
                                   "serve-nytimes-batch")
            for f in (None, "unchanged", "half", "altered", "dropped")])


@pytest.mark.parametrize("cell,fault", CASES)
def test_correct_only_when_nothing_is_broken(monkeypatch, cell, fault):
    _, _, _, traffic, _ = run.load_cell(cell)
    if traffic["job"] == "train":
        _train_fault(monkeypatch, fault)
    else:
        _serve_fault(monkeypatch, fault)
    result, checks = run.run_cell(cell, 2**31 + 97, 1.0, False,
                                  require_chip=False,
                                  overrides=SMALL[traffic["job"]])
    assert result["correct"] is (fault is None), checks
    assert list(result)[-1] == "device"
    assert result["attempted"] > 0
