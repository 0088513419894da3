"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases (compare logits against the oracle, serve every request to full
length) pass at smoke size with the kernels in interpret mode."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.configs import get_config, reduce_for_smoke

ROOT = Path(__file__).resolve().parents[1]


def test_exits_nonzero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_one_chip_phases_pass_at_smoke_size_in_interpret_mode():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke as cs
    finally:
        sys.path.remove(str(ROOT))
    base = reduce_for_smoke(get_config(cs.ARCH))
    plan = cs.Plan(slots=2, max_len=128, chunk=32, requests=3,
                   prompt_range=(20, 70), new_tokens=3,
                   forced_prompts=((0, 70), (1, 20)), forced_steps=2)
    _, model = cs.build(base, "interpret")
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    cs.run_one_chip(base, params, plan, seed=0, kernel="interpret")
