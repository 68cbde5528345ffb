"""The comparison that decides ``correct`` for a served model.

After the window, a sample of the requests the engine served tokens to,
finished or still in flight when the run ended, drawn from the seed and
always holding the one with the most served tokens,
is run once through the reference over its prompt and its served tokens.
At each served position the reference gives its logits; the number
compared is the widest gap by which a served token's reference logit
lies below the reference's best at that position. Greedy decoding in
bf16 picks a token whose logit is near the best; a wrong cache, a wrong
position or a wrong expert picks one far from it.
"""
from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp
import numpy as np


def sample(served: Sequence, rng: np.random.Generator, *,
           min_tokens: int, max_requests: int) -> List:
    """The request with the most served tokens, then others in the
    order ``rng`` gives them, until ``min_tokens`` served tokens or
    ``max_requests`` requests are in the sample."""
    if not served:
        return []
    order = sorted(served, key=lambda r: (-len(r.out_tokens), -len(r.prompt)))
    out, rest = [order[0]], list(order[1:])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    total = len(order[0].out_tokens)
    for r in rest:
        if total >= min_tokens or len(out) >= max_requests:
            break
        out.append(r)
        total += len(r.out_tokens)
    return out


def positions(req):
    """(tokens, rows, served): the sequence the reference reads, the rows
    whose logits chose each served token, and those tokens. The last
    served token was chosen but never read back, so it is compared and
    not fed."""
    prompt = np.asarray(req.prompt, np.int32)
    served = np.asarray(req.out_tokens, np.int32)
    tokens = np.concatenate([prompt, served[:-1]])
    rows = len(prompt) - 1 + np.arange(len(served))
    return tokens, rows, served


def gaps(ref_logits, chosen) -> np.ndarray:
    """Per row: the best reference logit less the reference logit of
    the token chosen at that row (0 where the choice is the best)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(chosen)[:, None],
                              axis=-1)[:, 0]
    return np.asarray(best - got)


def readings(all_gaps) -> dict:
    """The numbers compared, over every served position of the sample:
    ``logit_gap``, the widest gap, and ``mean_gap``, the mean."""
    g = np.concatenate(all_gaps)
    return {"logit_gap": float(g.max()), "mean_gap": float(g.mean())}
