"""The traffic generator: reproducible from the seed, lengths inside their
clips, the same work for every seed."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import generate  # noqa: E402

BIG_SEED = 2 ** 33 + 12345          # the driver's seeds exceed 32 bits


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


#: an open-loop chat mix (not a cell yet: its rate waits for a knee found
#: on the chip)
CHAT = {"name": "chat", "kind": "poisson", "slots": 64, "rate": 8.0,
        "max_len": 2048, "check_requests": 8,
        "prompt": {"dist": "lognormal", "median": 384, "sigma": 1.0,
                   "min": 32, "max": 1536},
        "output": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                   "min": 16, "max": 384}}


def _flat(streams):
    return [s for st in streams for s in st]


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_closed_reproducible_and_clipped(seed):
    mix = _mix("decode-long")
    a = generate.closed(mix, seed, vocab=64000)
    b = generate.closed(mix, seed, vocab=64000)
    assert len(a) == mix["clients"]
    assert all(len(s) == mix["requests_per_client"] for s in a)
    for x, y in zip(_flat(a), _flat(b)):
        assert x.rid == y.rid and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    p, o = mix["prompt"], mix["output"]
    for st in a:
        for j, s in enumerate(st):
            assert p["min"] <= len(s.prompt) <= p["max"]
            assert s.prompt.dtype == np.int32
            assert 0 <= s.prompt.min() and s.prompt.max() < 64000
            lo = 1 if j == 0 else o["min"]
            assert lo <= s.max_new_tokens <= o["max"]
    # each client's first request keeps a share of its drawn output
    firsts = sorted(st[0].max_new_tokens for st in a)
    assert firsts[0] < o["min"] and firsts[-1] <= o["max"]


def test_closed_same_work_every_seed():
    mix = _mix("decode-long")
    a = _flat(generate.closed(mix, 1, vocab=1000))
    b = _flat(generate.closed(mix, BIG_SEED, vocab=1000))
    assert sorted(len(s.prompt) for s in a) == sorted(len(s.prompt) for s in b)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    # each round (the j-th request of every client) is the same set: the
    # batch opens on the same prompts and remaining outputs for every seed
    c = mix["clients"]
    a, b = (sorted(r, key=lambda s: s.rid) for r in (a, b))
    for j in range(mix["requests_per_client"]):
        for key in (lambda s: len(s.prompt), lambda s: s.max_new_tokens):
            assert sorted(map(key, a[j * c:(j + 1) * c])) == \
                sorted(map(key, b[j * c:(j + 1) * c]))


def test_first_outputs_are_steady_state_residuals():
    """A request caught mid-flight has a length-biased output and has run
    a uniform share of it: for outputs uniform in 512-1024 the remaining
    part averages E[L^2] / (2 E[L]) = 398.2, is flat up to 512 and falls
    to 0 at 1024."""
    spec = {"dist": "uniform", "min": 512, "max": 1024}
    r = generate.residuals(spec, 512)
    assert list(r) == sorted(r) and 1 <= r[0] and r[-1] <= 1024
    assert abs(r.mean() - (512 ** 2 + 512 * 1024 + 1024 ** 2) / 3
               / (2 * 768)) < 3
    # flat below 512: 2/3 of the mass (512 / 768)
    assert abs((r <= 512).mean() - 2 / 3) < 0.01
    mix = _mix("decode-long")
    firsts = [st[0].max_new_tokens for st in generate.closed(mix, 3, 100)]
    assert sorted(firsts) == list(1 + generate.residuals(mix["output"],
                                                         mix["clients"]))


@pytest.mark.parametrize("seed", [3, BIG_SEED])
def test_poisson_reproducible_and_clipped(seed):
    mix = CHAT
    a = generate.poisson(mix, seed, vocab=49152, seconds=30.0)
    b = generate.poisson(mix, seed, vocab=49152, seconds=30.0)
    assert [(s.rid, s.due, s.max_new_tokens) for s in a] == \
        [(s.rid, s.due, s.max_new_tokens) for s in b]
    due = [s.due for s in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30.0
    # about rate x seconds requests, with exponential gaps
    assert len(a) == 240
    p, o = mix["prompt"], mix["output"]
    for s in a:
        assert p["min"] <= len(s.prompt) <= p["max"]
        assert o["min"] <= s.max_new_tokens <= o["max"]


def test_poisson_same_work_every_seed():
    mix = CHAT
    a = generate.poisson(mix, 1, vocab=100, seconds=30.0)
    b = generate.poisson(mix, 2, vocab=100, seconds=30.0)
    def gaps(r):
        return np.diff([0.0] + [s.due for s in r])

    assert len(a) == len(b)
    assert sorted(len(s.prompt) for s in a) == sorted(len(s.prompt)
                                                      for s in b)
    np.testing.assert_allclose(sorted(gaps(a)), sorted(gaps(b)), rtol=1e-9)
    assert list(gaps(a)) != list(gaps(b))


@pytest.mark.parametrize("spec,n", [
    ({"dist": "uniform", "min": 2048, "max": 3000}, 512),
    ({"dist": "lognormal", "median": 384, "sigma": 1.0, "min": 32,
      "max": 1536}, 1000),
])
def test_quantiles_follow_the_distribution(spec, n):
    q = generate.quantiles(spec, n)
    assert list(q) == sorted(q)
    assert q.min() >= spec["min"] and q.max() <= spec["max"]
    if spec["dist"] == "uniform":
        assert abs(q.mean() - (spec["min"] + spec["max"]) / 2) < 2
    else:
        assert abs(np.median(q) - spec["median"]) <= 2
