"""The system under test for one configuration: the program's model at the
configuration's sizes, its weights made on the device from the seed in the
serving dtype, and an ``EngineCore`` with the configuration's settings.

Everything here calls into the program (``repro``); the yardstick, the
reference and the traffic live elsewhere in ``bench/`` and import nothing
of it.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

#: configuration keys (as the published config.json names them) -> the
#: program's ``ModelConfig`` field that holds the same number
CONFIG_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_base",
    "rms_norm_eps": "norm_eps",
    "attention_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
}


def jax_key(seed: int):
    """A threefry key from any non-negative whole number (the driver's
    seeds do not fit 32 bits, which ``PRNGKey`` would need)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a benchmark configuration file:
    its registered architecture with every size the file states, polar
    keys and the page-native Pallas paths."""
    from repro.configs import get_config

    base = get_config(conf["arch"])
    over = {CONFIG_FIELDS[k]: v for k, v in conf["model"].items()
            if k in CONFIG_FIELDS}
    if conf["model"].get("hidden_act", "silu") != base.act:
        raise ValueError(f"{conf['name']}: activation "
                         f"{conf['model'].get('hidden_act')!r} is not the "
                         f"program's {base.act!r}")
    if conf["model"].get("mlp_bias"):
        raise ValueError(f"{conf['name']}: the program's feed-forward has "
                         "no biases")
    srv = conf["serving"]
    keys = srv["keys"]
    quant = dataclasses.replace(
        base.quant, method=keys["method"], rho_bits=keys["rho_bits"],
        theta_bits=keys["theta_bits"], group_size=keys["group_size"],
        value_bits=0)
    return dataclasses.replace(
        base, quant=quant, dtype=srv["dtype"],
        decode_backend=srv["attention_backend"],
        prefill_backend=srv["attention_backend"], **over)


def leaf_std(name: str, shape: tuple):
    """Standard deviation of one weight leaf under the program's own
    initializers (``models/layers.py``): 0.02 for the embedding,
    1/sqrt(fan_in) for projections and the head; None for norm gains
    (ones) and 0.0 for biases (zeros)."""
    if name == "embed":
        return 0.02
    if name.startswith("ln") or name.endswith("norm"):
        return None
    if name.startswith("b"):
        return 0.0
    return 1.0 / math.sqrt(shape[-2])


def make_weights(model, seed: int):
    """Random weights in the serving dtype, made on the device in one
    jitted call: the tree ``model.init`` would return, with the same
    distributions, but never an fp32 copy of the whole model."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(model.cfg.dtype)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for k, (path, sd) in zip(jax.random.split(key, len(flat)), flat):
            std = leaf_std(path[-1].key, sd.shape)
            if std is None:
                out.append(jnp.ones(sd.shape, dtype))
            elif std == 0.0:
                out.append(jnp.zeros(sd.shape, dtype))
            else:
                out.append(jax.random.normal(k, sd.shape, dtype)
                           * jnp.asarray(std, dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(build)(jax_key(seed)))


def build_engine(model, params, conf: dict, mix: dict):
    """The engine with the configuration's settings and the mix's
    context capacity."""
    from repro.serve.core import EngineCore

    srv = conf["serving"]
    return EngineCore(model, params, max_slots=mix["slots"],
                      max_len=mix["max_len"],
                      prefill_chunk=srv["prefill_chunk"],
                      runahead=srv["runahead"])


def warm_shapes(core, gen, prompt_range: tuple, out_range: tuple) -> int:
    """Compile the programs this traffic can reach, and no others: the
    prefill chunk at every table width a prompt in ``prompt_range`` can
    touch, and the decode step (plain and run-ahead) at every width a
    batch whose longest context lies in the reachable range can take.
    The programs run on the engine's own pool, through a table of
    scratch pages (a second pool would not fit beside the first at the
    larger cells), and the engine then starts a fresh session. Returns
    the number of programs run."""
    import jax
    import jax.numpy as jnp

    from repro.serve.scheduler import Scheduler

    lay = core.layout
    g, s, c = lay.page_size, lay.slots, core.prefill_chunk
    state, core.state = core.state, None
    table = Scheduler(lay).alloc.table()
    key = jax.random.PRNGKey(0)
    _, _ = jax.random.split(key)        # the engine's host-side key split

    pre_w = sorted({core._step_width(-(-min(off + c, p) // g))
                    for p in range(prompt_range[0], prompt_range[1] + 1)
                    for off in range(0, p, c)})
    lo = prompt_range[0] + 1
    hi = min(prompt_range[1] + out_range[1], lay.tokens_per_slot)
    dec_w = sorted({core._step_width(n // g + 1) for n in range(lo, hi + 1)})
    ra_w = sorted({core._step_width((min(n + core.runahead,
                                         lay.tokens_per_slot) - 1) // g + 1)
                   for n in range(lo, hi + 1)})
    runs = 0
    for w in pre_w:
        logits, state = core._prefill_chunk(
            core.params, jnp.zeros((1, c), jnp.int32), state,
            jnp.zeros((), jnp.int32), table[0][:w],
            jnp.zeros((), jnp.int32), jnp.asarray(c, jnp.int32))
        jax.block_until_ready(core._sample(logits, key, gen))
        runs += 1
    for w in dec_w:
        logits, state = core._decode(
            core.params, state, jnp.zeros((s,), jnp.int32),
            table[:, :w], jnp.zeros((s,), bool))
        jax.block_until_ready(core._sample(logits, key, gen))
        runs += 1
    if core.runahead > 1:
        for w in ra_w:
            out = core._runahead_fn(
                core.params, state, jnp.zeros((s,), jnp.int32),
                table[:, :w], jnp.zeros((s,), bool), key,
                jnp.zeros((s,), jnp.int32), jnp.zeros((s,), bool),
                horizon=core.runahead, temperature=gen.temperature,
                top_k=gen.top_k, eos_id=gen.eos_id)
            state = out[1]
            jax.block_until_ready(out[0])
            runs += 1
    jax.block_until_ready(state)
    del state
    core.reset(gen)
    return runs
