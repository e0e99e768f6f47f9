"""A run with the timed path broken underneath comes out not correct: the
chip check is skipped, everything else of a run is driven at a size the
CPU holds.  Faults: an answer altered where it is produced; half of the
batch left out of a mean, the mean taken over the rest.

A full cell compares every row, so one altered answer is enough there.  A
serving cell compares a seeded sample of the answered rows, so its fault
alters every answer: one altered row would be seen only when a sampled
request asks for it."""
import time

import jax.numpy as jnp
import pytest

from bench import harness


def _alter_one_answer(monkeypatch):
    from repro.core.pipeline import StageGraphExecutor

    head = StageGraphExecutor.head

    def altered(self, params, z, batch=None):
        out = head(self, params, z, batch)
        return out.at[0, 0].add(1e-3 * jnp.max(jnp.abs(out)))

    monkeypatch.setattr(StageGraphExecutor, "head", altered)


def _alter_every_answer(monkeypatch):
    from repro.core.pipeline import StageGraphExecutor

    head = StageGraphExecutor.head

    def altered(self, params, z, batch=None):
        out = head(self, params, z, batch)
        return out.at[:, 0].add(1e-3 * jnp.max(jnp.abs(out)))

    monkeypatch.setattr(StageGraphExecutor, "head", altered)


def _half_rows_in_semantic_mean(monkeypatch):
    from repro.core import semantics

    full = semantics.semantic_attention

    def half(p, z, mask=None):
        n = z.shape[1]
        m = jnp.ones((n,), z.dtype) if mask is None else mask
        m = m * (jnp.arange(n) < (n + 1) // 2)
        return full(p, z, m)

    monkeypatch.setattr(semantics, "semantic_attention", half)


def _half_neighbors_in_mean(monkeypatch):
    from repro.core import stages

    full = stages.mean_aggregate_padded

    def half(h_src, nbr, mask, hn=None):
        k = mask.shape[1]
        return full(h_src, nbr, mask * (jnp.arange(k) < (k + 1) // 2), hn)

    monkeypatch.setattr(stages, "mean_aggregate_padded", half)


@pytest.mark.parametrize("model,traffic,workload,fault", [
    ("han", "full", "han_imdb.full", _alter_one_answer),
    ("han", "serve_zipf", "han_imdb.serve_zipf", _half_rows_in_semantic_mean),
    ("rgcn", "full", "rgcn_imdb.full", _half_neighbors_in_mean),
    ("rgcn", "serve_sat", "rgcn_imdb.serve_sat", _alter_every_answer),
])
def test_broken_timed_path_is_not_correct(cell, monkeypatch, model, traffic,
                                          workload, fault):
    fault(monkeypatch)
    out = harness.run_cell(cell(model, traffic, workload), 11, 0.3, False,
                           time.perf_counter())
    assert not out["correct"]
    err = out["checks"]["logit_rel_err"]
    assert err["value"] > err["limit"]
