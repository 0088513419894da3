"""Plain reference of a dense decoder with grouped-query attention (the
Llama architecture that Yi-9B and Granite-8B-Code use): pre-RMSNorm
blocks, rotary position embedding with half-split pairs, causal softmax
attention with ``num_attention_heads / num_key_value_heads`` query heads
per key/value head, a SwiGLU feed-forward and an untied output head.

It runs the whole sequence at once, in float32 at matmul precision
"highest", with no cache, no kernel and no batching, layer by layer and in
blocks of query rows so that it fits beside the weights. It imports nothing
of the program. The weights are the benchmark's own (made from the seed),
held in the serving dtype and widened to float32 here.

The configuration serves its keys PolarQuant-coded (arXiv:2502.00527):
per group of ``group_size`` consecutive tokens and per rotary pair
(dims j and j + hd/2), the radius and the angle atan2(y, x) + pi are each
coded on ``rho_bits`` / ``theta_bits`` levels between the group's minimum
and maximum (mid-rise: code floor((v - min) / s), value (code + 1/2) s +
min, s = (max - min) / 2^bits), and a key reads back as radius times
(cos, sin)(angle - pi). A query sees the coded keys of the groups that
were complete when it was computed and the exact keys after them: a
prompt position t, computed in its prefill chunk, sees coded keys before
the chunk's start (t // chunk * chunk); a generated position t sees coded
keys before ((t + 1) // group_size) * group_size. The reference computes
exactly that, so it is held to the configuration it serves.

``precision="fp8"`` is the control: every matmul operand rounded to
float8 e4m3 with a scale per tensor (weights) or per row (activations),
accumulated in float32. It stands for the step below the configuration's
bfloat16 that a later change might be tempted to take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: query rows per attention / output-head block
ROWS = 512


def _fp8(x, axis):
    """Round to float8 e4m3 under a scale that maps the largest magnitude
    along ``axis`` to the format's largest finite value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, fp8: bool):
    """a (..., K) @ w (K, N) in float32; fp8 rounds both operands."""
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, base):
    """x (H, T, hd) at positions pos (T,): dims j and j + hd/2 rotate
    together by pos * base^(-2j/hd)."""
    hd = x.shape[-1]
    inv = base ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _polar(k, g: int, rbits: int, tbits: int):
    """Keys (H, T, hd), T a multiple of g, as their PolarQuant codes read
    back, group by group."""
    h, t, hd = k.shape
    kg = k.reshape(h, t // g, g, hd)
    x, y = kg[..., : hd // 2], kg[..., hd // 2:]
    rho = jnp.sqrt(x * x + y * y)
    theta = jnp.where(rho > 0, jnp.arctan2(y, x) + jnp.pi, jnp.pi)

    def code(v, bits):
        lo = jnp.min(v, axis=2, keepdims=True)
        hi = jnp.max(v, axis=2, keepdims=True)
        s = jnp.maximum((hi - lo) / (1 << bits), 1e-8)
        c = jnp.clip(jnp.floor((v - lo) / s), 0, (1 << bits) - 1)
        return (c + 0.5) * s + lo

    r, a = code(rho, rbits), code(theta, tbits) - jnp.pi
    return jnp.concatenate([r * jnp.cos(a), r * jnp.sin(a)], -1).reshape(
        h, t, hd)


@functools.partial(jax.jit, static_argnames=("m", "keys", "fp8"))
def _layer(x, layers, i, n_real, coded_before, m: tuple, keys: tuple,
           fp8: bool):
    """One decoder block over the padded sequence x (T, d). The query at
    position t sees the coded keys of positions before coded_before[t]."""
    d, nh, nkv, hd, eps, base = m
    lw = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False)
        .astype(jnp.float32), layers)
    t = x.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    h = _rms(x, lw["ln1"], eps)
    a = lw["attn"]
    q = _mm(h, a["wq"], fp8).reshape(t, nh, hd).transpose(1, 0, 2)
    k = _mm(h, a["wk"], fp8).reshape(t, nkv, hd).transpose(1, 0, 2)
    v = _mm(h, a["wv"], fp8).reshape(t, nkv, hd).transpose(1, 0, 2)
    if "bq" in a:
        q = q + a["bq"].reshape(nh, 1, hd)
        k = k + a["bk"].reshape(nkv, 1, hd)
        v = v + a["bv"].reshape(nkv, 1, hd)
    q = _rope(q, pos, base) / np.sqrt(hd)
    k = _rope(k, pos, base)
    kq = _polar(k, *keys)
    rep = nh // nkv
    k = jnp.repeat(k, rep, axis=0)                          # (nh, T, hd)
    kq = jnp.repeat(kq, rep, axis=0)
    v = jnp.repeat(v, rep, axis=0)
    if fp8:
        k, kq, v = _fp8(k, -1), _fp8(kq, -1), _fp8(v, -2)

    def block(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, ROWS, axis=1)
        if fp8:
            qb = _fp8(qb, -1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST)
        sq = jnp.einsum("hqd,hkd->hqk", qb, kq,
                        precision=jax.lax.Precision.HIGHEST)
        qi = qb_start + jnp.arange(ROWS)[:, None]
        ki = jnp.arange(t)[None, :]
        cb = jax.lax.dynamic_slice_in_dim(coded_before, qb_start, ROWS)
        s = jnp.where(ki < cb[:, None], sq, s)
        s = jnp.where((ki <= qi) & (ki < n_real), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _fp8(p, -1)
        return jnp.einsum("hqk,hkd->hqd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, jnp.arange(0, t, ROWS))          # (B, nh, R, hd)
    o = o.transpose(0, 2, 1, 3).reshape(t, nh * hd)
    x = x + _mm(o, a["wo"], fp8)
    h = _rms(x, lw["ln2"], eps)
    f = lw["ffn"]
    g = _mm(h, f["wg"], fp8)
    u = _mm(h, f["wu"], fp8)
    return x + _mm(jax.nn.silu(g) * u, f["wd"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, norm, head, pick, eps: float, fp8: bool):
    """Per row of x: the largest logit, the logit of ``pick`` and the
    arg-max token."""
    h = _rms(x, norm.astype(jnp.float32), eps)
    lg = _mm(h, head.astype(jnp.float32), fp8)              # (R, V)
    at = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return jnp.max(lg, -1), at, jnp.argmax(lg, -1).astype(jnp.int32)


def coded_before(t: int, prompt_len: int, chunk: int, group: int):
    """Per position, where the exact keys it sees begin (see the module
    docstring)."""
    pos = np.arange(t)
    return np.where(pos < prompt_len, pos // chunk * chunk,
                    (pos + 1) // group * group).astype(np.int32)


def score(weights: dict, model: dict, serving: dict, tokens: np.ndarray,
          prompt_len: int, pick: np.ndarray, pad_to: int,
          precision: str = "float32"):
    """Run the reference over ``tokens`` (T,), a prompt of ``prompt_len``
    tokens and what was generated after it, and read, at every position
    t, the largest logit of the next token, the logit of ``pick[t]`` and
    the arg-max token. ``pad_to`` (rounded up to whole blocks of
    :data:`ROWS`) fixes the compiled length, so one program serves every
    sequence.

    ``model`` holds the configuration's sizes under their published names,
    ``serving`` its prefill chunk and key code. Returns three numpy arrays
    of length T."""
    fp8 = {"float32": False, "fp8": True}[precision]
    t = len(tokens)
    if t > pad_to:
        raise ValueError(f"sequence of {t} tokens does not fit pad_to "
                         f"{pad_to}")
    pad_to = -(-pad_to // ROWS) * ROWS
    d = model["hidden_size"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    m = (d, nh, nkv, model.get("head_dim", d // nh), model["rms_norm_eps"],
         float(model["rope_theta"]))
    kc = serving["keys"]
    if kc["method"] != "polar":
        raise ValueError(f"no reference for key code {kc['method']!r}")
    keys = (kc["group_size"], kc["rho_bits"], kc["theta_bits"])
    if pad_to % keys[0]:
        raise ValueError(f"pad_to {pad_to} is not whole groups")
    cb = np.zeros((pad_to,), np.int32)
    cb[:t] = coded_before(t, prompt_len, serving["prefill_chunk"], keys[0])
    toks = np.zeros((pad_to,), np.int32)
    toks[:t] = tokens
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["embed"], jnp.asarray(toks), axis=0).astype(
            jnp.float32)
        for i in range(model["num_hidden_layers"]):
            x = _layer(x, weights["layers"], jnp.asarray(i), jnp.asarray(t),
                       jnp.asarray(cb), m=m, keys=keys, fp8=fp8)
        pk = np.zeros((pad_to,), np.int32)
        pk[:t] = pick
        outs = [_head(x[r:r + ROWS], weights["final_norm"],
                      weights["lm_head"], jnp.asarray(pk[r:r + ROWS]),
                      eps=m[4], fp8=fp8)
                for r in range(0, pad_to, ROWS)]
    mx, at, am = (np.concatenate([np.asarray(o[j]) for o in outs])[:t]
                  for j in range(3))
    return mx, at, am
