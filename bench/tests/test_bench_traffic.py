"""The seeded traffic generator."""
import numpy as np

from bench import traffic

OPEN = {"kind": "open", "rate_per_s": 400, "targets_min": 1,
        "targets_max": 8, "ids": "zipf", "zipf_s": 0.99, "base_seed": 20}
CLOSED = {"kind": "closed", "clients": 32, "targets_min": 1,
          "targets_max": 8, "ids": "uniform", "base_seed": 30}


def _flat(plan):
    return [(p["due"], tuple(p["targets"])) for p in plan]


def test_same_seed_same_schedule():
    a = traffic.open_loop(OPEN, 5.0, 4278, 2**40 + 3)
    b = traffic.open_loop(OPEN, 5.0, 4278, 2**40 + 3)
    assert _flat(a) == _flat(b)
    assert _flat(a) != _flat(traffic.open_loop(OPEN, 5.0, 4278, 7))


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = traffic.open_loop(OPEN, 5.0, 4278, 1)
    b = traffic.open_loop(OPEN, 5.0, 4278, 2)
    assert len(a) == len(b) == 2000
    assert sorted(len(p["targets"]) for p in a) == sorted(
        len(p["targets"]) for p in b)
    ga = np.diff([0.0] + [p["due"] for p in a])
    gb = np.diff([0.0] + [p["due"] for p in b])
    assert np.allclose(np.sort(ga), np.sort(gb), rtol=1e-9, atol=1e-15)
    assert 0.0 < a[0]["due"] and a[-1]["due"] == b[-1]["due"] < 5.0
    assert all(1 <= len(p["targets"]) <= 8 for p in a)


def test_zipf_top_id_share():
    n, s = 4278, 0.99
    p = traffic.zipf_probs(n, s)
    assert abs(p[0] - 1.0 / np.sum(1.0 / np.arange(1, n + 1) ** s)) < 1e-12
    ids = np.concatenate(traffic.Ids(OPEN, n, np.random.default_rng(3))
                         .draw(np.full(20000, 10)))
    top = np.bincount(ids, minlength=n).max() / len(ids)
    assert abs(top - p[0]) < 0.1 * p[0]  # about 11% of all ids
    assert 0.10 < p[0] < 0.12


def test_closed_loop_is_seeded_and_cycles_clients():
    a = traffic.ClosedLoop(CLOSED, 4278, 9)
    b = traffic.ClosedLoop(CLOSED, 4278, 9)
    for _ in range(3):
        xa, xb = a.next_batch(), b.next_batch()
        assert len(xa) == 32
        assert all(np.array_equal(u, v) for u, v in zip(xa, xb))
        assert all(1 <= len(u) <= 8 and u.max() < 4278 for u in xa)
