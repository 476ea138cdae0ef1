"""Traffic drawn from a file of parameters and the seed."""
import collections

import numpy as np

import tiny  # noqa: F401
from bench import traffic

CHAT = {"arrival": "poisson", "rate_per_s": 8.0,
        "prompt_lens": [[128, 0.4], [256, 0.3], [512, 0.2], [1024, 0.1]],
        "output_lens": [[128, 0.3], [256, 0.3], [512, 0.25],
                        [1024, 0.15]], "block": 20}


def _key(reqs):
    return [(r.due_s, len(r.prompt), r.max_new, r.prompt[:4].tolist())
            for r in reqs]


def test_same_seed_same_requests():
    a = traffic.generate(CHAT, 2 ** 40 + 1, 30.0, 1000)
    b = traffic.generate(CHAT, 2 ** 40 + 1, 30.0, 1000)
    assert _key(a) == _key(b)


def test_seeds_share_the_work_in_another_order():
    a = traffic.generate(CHAT, 1, 50.0, 1000)
    b = traffic.generate(CHAT, 2, 50.0, 1000)
    assert _key(a) != _key(b)
    na, nb = len(a), len(b)
    assert abs(na - nb) <= 20             # at most one block apart
    n = min(na, nb) // 20 * 20
    ca = collections.Counter((len(r.prompt), r.max_new) for r in a[:n])
    cb = collections.Counter((len(r.prompt), r.max_new) for r in b[:n])
    assert sorted(c for c, _ in ca) == sorted(c for c, _ in cb)
    assert collections.Counter(len(r.prompt) for r in a[:n]) \
        == collections.Counter(len(r.prompt) for r in b[:n])


def test_poisson_rate_and_window():
    reqs = traffic.generate(CHAT, 3, 100.0, 1000)
    dues = np.array([r.due_s for r in reqs])
    assert np.all(np.diff(dues) > 0) and dues[-1] < 100.0
    assert abs(len(reqs) / 100.0 - 8.0) < 0.8
    mean_out = np.mean([r.max_new for r in reqs[:780]])
    assert abs(mean_out - 396.8) < 1.0


def test_backlog():
    spec = dict(CHAT, arrival="backlog", requests=45)
    reqs = traffic.generate(spec, 4, 10.0, 1000)
    assert len(reqs) == 45 and all(r.due_s == 0.0 for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)


def test_shapes():
    assert traffic.shapes(CHAT) == ([128, 256, 512, 1024], 2048)
