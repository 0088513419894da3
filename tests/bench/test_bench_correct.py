"""``correct`` comes out false for the control and for each fault the
cells can have, planted in the timed path under the benchmark's own run()
at a size the CPU can run (see bench_tiny.py for the readings the tiny
limit was set from)."""
import argparse
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import bench_tiny  # noqa: E402

sys.path.insert(0, bench_tiny.ROOT)
from bench import check, run  # noqa: E402

CELL = "yi-9b.decode-long"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


def stale_state(core):
    """Every decode step returns the cache state it was given."""
    import jax

    m = core.model
    core._decode = jax.jit(
        lambda p, s, t, tb, a: (m.decode_paged(p, s, t, tb, a)[0], s))

    def runahead(p, s, t, tb, a, k, rem, done, **kw):
        out = m.decode_runahead(p, s, t, tb, a, k, rem, done, **kw)
        return (out[0], s) + tuple(out[2:])

    core._runahead_fn = jax.jit(runahead, static_argnames=(
        "horizon", "temperature", "top_k", "eos_id"))


def altered_token(core):
    """Every token is altered where it is sampled."""
    v = core.model.cfg.vocab_size
    sample, runahead = core._sample, core._runahead_fn
    core._sample = lambda lg, key, gen: (sample(lg, key, gen) + 1) % v

    def ra(*a, **kw):
        out = runahead(*a, **kw)
        return ((out[0] + 1) % v, out[1], (out[2] + 1) % v) + tuple(out[3:])

    core._runahead_fn = ra


def half_batch(core):
    """Decode steps run only the even slots; the odd ones keep their
    don't-care logits and stale caches."""
    import jax.numpy as jnp

    keep = jnp.arange(core.layout.slots) % 2 == 0
    decode, runahead = core._decode, core._runahead_fn
    core._decode = lambda p, s, t, tb, a: decode(p, s, t, tb, a & keep)
    core._runahead_fn = lambda p, s, t, tb, a, *r, **kw: runahead(
        p, s, t, tb, a & keep, *r, **kw)


@pytest.mark.parametrize("fault", [stale_state, altered_token, half_batch])
def test_fault_is_not_correct(root, fault):
    args = argparse.Namespace(workload=CELL, seed=2, seconds=3.0, trace=0)
    res = run.run(args, root=root, require_tpu=False,
                  hooks={"engine": fault})
    gap = res["checks"]["widest_logit_gap"]
    assert not res["correct"]
    assert gap["value"] > gap["limit"], gap


def test_control_is_not_correct(root):
    """The reference in float8, put in the program's place on the same
    prompts and served tokens, is judged by run()'s own ``correct`` and
    fails it; the program itself, on the same seed, passes."""
    args = argparse.Namespace(workload=CELL, seed=1, seconds=3.0, trace=0)
    res = run.run(args, root=root, require_tpu=False,
                  hooks={"gap": check.control_gap})
    gap = res["checks"]["widest_logit_gap"]
    assert not res["correct"]
    assert gap["value"] > gap["limit"], gap
    res = run.run(args, root=root, require_tpu=False)
    assert res["correct"], res["checks"]
