"""Operations and bytes the algorithm needs, from the configuration's sizes
and the live lengths of each call: never from the table width a kernel
walks, so the count does not depend on how a kernel covers pages.

``model`` is a configuration's ``model`` object (published key names) and
``kv`` its ``serving.keys`` object. Attention over the cached prefix reads
each live page of every key/value head once: polar codes (one byte per
channel pair and token), four float32 statistics per channel pair, and the
value rows in the serving dtype. Operations count a multiply and an add as
two: query-key scores and probability-value sums, 2 * head_dim each per
query head and key.
"""
from __future__ import annotations

import numpy as np


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return {"d": d, "nh": nh, "nkv": nkv,
            "hd": model.get("head_dim", d // nh),
            "ff": model["intermediate_size"], "vocab": model["vocab_size"],
            "layers": model["num_hidden_layers"]}


def page_bytes(model: dict, kv: dict) -> int:
    """Bytes of one page (one quantization group) of one key/value head."""
    m = dims(model)
    g, pairs = kv["group_size"], m["hd"] // 2
    code_bytes = -(-(kv["rho_bits"] + kv["theta_bits"]) // 8)
    return (g * pairs * code_bytes + 4 * pairs * kv["stat_bytes"]
            + g * m["hd"] * kv["value_bytes"])


def decode_attention(model: dict, kv: dict, contexts) -> tuple[float, float]:
    """(flops, bytes) of the paged decode kernel over all layers, for one
    decode token per entry of ``contexts`` (the slot's length after the
    token's append). The kernel covers the flushed pages, whole groups of
    ``group_size`` tokens; the rolling remainder is merged outside it."""
    m = dims(model)
    g = kv["group_size"]
    pages = np.asarray(contexts, np.int64) // g
    n_tok = pages.size
    flops = 4.0 * m["nh"] * m["hd"] * g * pages.sum()
    io = n_tok * m["nh"] * m["hd"] * 4 * 2          # query in, output out
    byts = m["nkv"] * page_bytes(model, kv) * pages.sum() + io
    return flops * m["layers"], float(byts) * m["layers"]


def prefill_attention(model: dict, kv: dict, chunks) -> tuple[float, float]:
    """(flops, bytes) of the paged prefill kernel over all layers, for
    chunks given as (start, length): the chunk's queries against the
    cached prefix ``[0, start)`` and, causally, against themselves."""
    m = dims(model)
    g = kv["group_size"]
    flops = byts = 0.0
    for start, clen in chunks:
        keys = clen * start + clen * (clen + 1) / 2
        flops += 4.0 * m["nh"] * m["hd"] * keys
        byts += (m["nkv"] * page_bytes(model, kv) * (start // g)
                 + 4 * clen * m["hd"] * (2 * m["nh"] + 2 * m["nkv"]))
    return flops * m["layers"], byts * m["layers"]


def layer_params(model: dict) -> int:
    m = dims(model)
    attn = m["d"] * m["hd"] * (2 * m["nh"] + 2 * m["nkv"])
    return attn + 3 * m["d"] * m["ff"] + 2 * m["d"]


def model_flops(model: dict, decode_contexts, chunks) -> float:
    """Model operations of every token processed: 2 per weight of the
    layers for each token, the output head for each token whose logits
    are computed (every decode token, the last row of a prefill chunk),
    and attention at each token's live context."""
    m = dims(model)
    dense = 2.0 * layer_params(model) * m["layers"]
    head = 2.0 * m["d"] * m["vocab"]
    ctx = np.asarray(decode_contexts, np.int64)
    f = ctx.size * (dense + head) + (4.0 * m["nh"] * m["hd"] * m["layers"]
                                     * ctx.sum())
    for start, clen in chunks:
        f += clen * dense + head + 4.0 * m["nh"] * m["hd"] * m["layers"] * (
            clen * start + clen * (clen + 1) / 2)
    return float(f)


def roofline_share(flops: float, byts: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """Least time the chip could take over the time measured, in %, and
    which of operations or bytes bounds that least time."""
    tf = flops / peak["bf16_flops"]
    tb = byts / peak["hbm_bytes_per_s"]
    return 100.0 * max(tf, tb) / seconds, ("flops" if tf >= tb else "bytes")
