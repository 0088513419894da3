"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine served,
finished or still in flight, drawn from the seed and holding the one with
the most served tokens, is run through the configuration's plain
reference: each prompt with its served tokens, teacher-forced, in float32, with the keys coded as the
configuration serves them. At every served token the
reference gives its best logit and the logit of the token the engine
served; the number compared is the widest gap between the two over the
whole sample. The traffic is greedy, so a sound engine serves the
reference's best token up to the rounding of the serving dtype and the
coding of the keys; a gap wider than the configuration's limit is a token
the system got wrong.

The served tokens were made by the timed path itself: chunked prefill
through the paged prefill kernel (every first token), and decode through
the paged decode kernel, run-ahead scans included, over polar-coded pages
at the cell's slots and lengths.
"""
from __future__ import annotations

import numpy as np

from bench.generate import rng_for


def sample(served: list, k: int, seed: int) -> list:
    """The request with the most served tokens, and ``k - 1`` more drawn
    from the seed among the rest."""
    done = sorted(served, key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.out_tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = rng_for(seed, 2)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _context(req):
    prompt = np.asarray(req.prompt, np.int32)
    out = np.asarray(req.out_tokens, np.int32)
    ctx = np.concatenate([prompt, out[:-1]])
    pick = np.zeros(len(ctx), np.int32)
    pick[len(prompt) - 1:] = out
    return ctx, pick, len(prompt) - 1


def served_gap(ref, weights, conf: dict, reqs: list, pad_to: int):
    """Widest gap, over every served token of ``reqs``, between the
    reference's best logit and its logit of the served token; and the
    number of tokens compared."""
    widest, n = 0.0, 0
    for r in reqs:
        ctx, pick, first = _context(r)
        mx, at, _ = ref.score(weights, conf["model"], conf["serving"], ctx,
                              first + 1, pick, pad_to)
        widest = max(widest, float(np.max(mx[first:] - at[first:])))
        n += len(ctx) - first
    return widest, n


def control_gap(ref, weights, conf: dict, reqs: list, pad_to: int):
    """The control: the reference in float8 put in the engine's place. At
    each position of the same prompts and served tokens, the float32
    reference's gap of the token that float8 puts first; the widest, and
    the number of tokens compared."""
    widest, n = 0.0, 0
    for r in reqs:
        ctx, pick, first = _context(r)
        _, _, am = ref.score(weights, conf["model"], conf["serving"], ctx,
                             first + 1, pick, pad_to, precision="fp8")
        alt = pick.copy()
        alt[first:] = am[first:]
        mx, at, _ = ref.score(weights, conf["model"], conf["serving"], ctx,
                              first + 1, alt, pad_to)
        widest = max(widest, float(np.max(mx[first:] - at[first:])))
        n += len(ctx) - first
    return widest, n
