"""The executor's one layer walk (``StageGraphExecutor._walk``).

``forward`` runs every stage inline under its named scope; ``stage_fns``
runs every stage as its own jit on the concrete output of the stage
before.  Over the executor's configurations, chaining the per-stage jits
gives the jitted forward's logits bit for bit, and the compiled forward's
ops name exactly the stages ``stage_fns`` returns (the per-stage split of
a device trace reads these names).
"""
import re

import jax
import numpy as np
import pytest

from conftest import WALK_IDS, WALK_MATRIX
from repro.configs.base import HGNNConfig
from repro.core.models import get_model
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET


def _built(tiny_hg, model, kw):
    DATASET_METAPATHS["tiny"] = [["M", "D", "M"], ["M", "A", "M"]]
    DATASET_TARGET["tiny"] = "M"
    kw = {"max_degree": 48, "max_instances": 4, "fused": True, **kw}
    m = get_model(HGNNConfig(model=model, dataset="tiny", hidden=16,
                             n_heads=4, n_classes=3, **kw))
    batch = m.prepare(tiny_hg)
    return m, m.init(jax.random.key(0), batch), batch


@pytest.mark.parametrize("model,kw", WALK_MATRIX, ids=WALK_IDS)
def test_stage_walk_matches_forward(tiny_hg, model, kw):
    m, params, batch = _built(tiny_hg, model, kw)
    # the stage jits close over the batch, so the forward does too: XLA
    # folds a captured index table as a constant, which rounds R-GCN's
    # degree normalization differently from the batch-as-argument program
    want = np.asarray(jax.jit(lambda p: m.forward(p, batch))(params))
    fns = m.executor.stage_fns(params, batch)
    names = list(fns)
    # within a layer each stage reads the output of the one before it; the
    # handoff between layers is in no stage
    for a, b in zip(names, names[1:]):
        if b.endswith("FP"):
            continue
        fn, args = fns[a]
        jax.tree.map(np.testing.assert_array_equal, fn(*args), fns[b][1][-1])
    fn, args = fns["head"]
    np.testing.assert_array_equal(np.asarray(fn(*args)), want)


@pytest.mark.parametrize("model,kw", WALK_MATRIX, ids=WALK_IDS)
def test_forward_scopes_name_the_stages(tiny_hg, model, kw):
    m, params, batch = _built(tiny_hg, model, kw)
    text = jax.jit(m.forward).lower(params, batch).compile().as_text()
    found = set(re.findall(r'op_name="jit\(forward\)/([^/"]+)', text))
    plan = m.executor.plan
    want = set()
    for name in m.executor.stage_fns(params, batch):
        layer = re.match(r"L(\d+)\.FP$", name)
        if layer and plan.layers[int(layer.group(1)) - 1].fp.kind == \
                "identity":
            continue  # an identity FP (R-GCN's hidden layers) emits no op
        want.add(name)
    assert found == want
