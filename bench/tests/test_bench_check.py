"""The check that decides ``correct``, at a size the CPU holds: the timed
path passes the committed limit, and the controls (the reference with
lower-precision matrix products) fail it.  One bf16 pass fails every
cell's limit; three bf16 passes (XLA's ``high``) fail R-GCN's, the only
limits that the chip readings let sit below it."""
import time

import numpy as np
import pytest

from bench import harness, reference


@pytest.mark.parametrize("model,traffic,workload,caught", [
    ("han", "full", "han_imdb.full", ("bf16",)),
    ("han", "serve_zipf", "han_imdb.serve_zipf", ("bf16",)),
    ("rgcn", "full", "rgcn_imdb.full", ("high", "bf16")),
    ("rgcn", "serve_sat", "rgcn_imdb.serve_sat", ("high", "bf16")),
])
def test_timed_path_passes_and_control_fails(cell, model, traffic, workload,
                                             caught):
    c = cell(model, traffic, workload)
    out = harness.run_cell(c, 2**33 + 5, 0.3, False, time.perf_counter(),
                           control=True)
    limit = c["limits"]["logit_rel_err"]
    assert out["correct"], out["checks"]
    assert out["checks"]["logit_rel_err"]["value"] <= limit
    for precision in caught:
        assert out["_notes"]["control_rel_err"][precision] > limit
    assert out["_notes"]["compared"] > 0
    assert out["_notes"]["recompiles_after_warmup"] == 0


def test_validate_edges_refuses_a_foreign_edge():
    import scipy.sparse as sp

    adj = sp.csr_matrix(np.array([[1, 1, 0], [0, 1, 1], [1, 0, 1]],
                                 np.float32))
    ids = np.arange(3)
    dst, src = np.array([0, 0, 1]), np.array([0, 1, 2])
    reference.validate_edges(dst, src, 3, 3, ids, ids, adj, 2, False)
    with pytest.raises(reference.BadEdges):  # (0, 2) is not an edge
        reference.validate_edges(np.array([0]), np.array([2]), 3, 3, ids,
                                 ids, adj, 2, False)
    with pytest.raises(reference.BadEdges):  # row 0 keeps 1 of min(2, 2)
        reference.validate_edges(np.array([0, 1, 1]), np.array([0, 1, 2]),
                                 3, 3, ids, ids, adj, 2, True)
    with pytest.raises(reference.BadEdges):  # an edge kept twice
        reference.validate_edges(np.array([0, 0]), np.array([1, 1]), 3, 3,
                                 ids, ids, adj, 2, False)
