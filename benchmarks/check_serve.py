"""The comparison that decides ``correct`` for a fill-mask serving cell.

Once the window has closed, a sample of the requests it finished is drawn
from the seed (the longest in it). The plain reference runs once over each
prompt (the ids the generator made, padded to the model's length under a pad
mask, float32) and reads, at every ``[MASK]`` position, how far the logit of
the token the server put FIRST lies below the reference's best:

- ``top1_gap``: the widest such gap over the sample, in units of the
  reference's logit spread at that position (standard deviation over the
  vocabulary), so that one limit holds whatever the weights' scale.
- ``unanswered``: sampled requests whose answer is malformed (exact: 0).

The control reads the same number for the token that the float8 reference
puts first (it need not serve).
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def draw_sample(finished: Sequence[int], lengths: Sequence[int], size: int, seed: int) -> List[int]:
    """Indices into the request pool: ``size`` distinct finished requests
    drawn from the seed, the longest finished one among them."""
    distinct = sorted(set(finished))
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    longest = max(distinct, key=lambda i: (lengths[i], -i))
    rest = [i for i in distinct if i != longest]
    picked = rng.choice(len(rest), size=min(size - 1, len(rest)), replace=False)
    return [longest] + [rest[int(j)] for j in picked]


def reference_mask_logits(logits_fn: Callable, params, requests: Sequence[Dict[str, Any]],
                          width: int, block_rows: int) -> List[np.ndarray]:
    """Per request, the reference's (masks, vocab) logits at its mask
    positions, computed ``block_rows`` requests at a time."""
    fn = jax.jit(logits_fn)
    out: List[np.ndarray] = []
    for lo in range(0, len(requests), block_rows):
        block = list(requests[lo:lo + block_rows])
        rows = len(block)
        block += [block[0]] * (block_rows - rows)  # one shape, one program
        ids = np.zeros((block_rows, width), np.int32)
        pad = np.ones((block_rows, width), bool)
        for r, req in enumerate(block):
            n = len(req["ids"])
            ids[r, :n], pad[r, :n] = req["ids"], False
        logits = np.asarray(fn(params, jnp.asarray(ids), jnp.asarray(pad)))
        for r, req in enumerate(block[:rows]):
            out.append(logits[r, req["mask_positions"]].astype(np.float64))
    return out


def gaps_below_best(ref_logits: Sequence[np.ndarray],
                    first_tokens: Sequence[Optional[Sequence[int]]]) -> Dict[str, Any]:
    """``first_tokens[i]``: the token id put first at each mask of request i,
    or None for a malformed answer."""
    worst, unanswered, tokens = 0.0, 0, 0
    for logits, firsts in zip(ref_logits, first_tokens):
        if firsts is None or len(firsts) != len(logits):
            unanswered += 1
            continue
        for row, tok in zip(logits, firsts):
            gap = (row.max() - row[int(tok)]) / row.std()
            worst = max(worst, float(gap))
            tokens += 1
    return {"top1_gap": worst, "unanswered": unanswered, "tokens_compared": tokens}


def load_limits(workload: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def verdict(numbers: Dict[str, Any], limits: Dict[str, float]) -> Dict[str, Any]:
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    compared["unanswered"] = {"value": numbers["unanswered"], "limit": 0}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in compared.values())
    return {"correct": bool(ok and numbers["tokens_compared"] > 0), "compared": compared}
