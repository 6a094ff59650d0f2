"""The one general generator: a traffic mix is a data file
(``benchmarks/traffic/<mix>.json``) of parameters, and everything drawn from
it comes from ``--seed``.

A mix names its ``loop`` (the load generator that drives it, a module of
``benchmarks/loops``) and its parameters. For training loops:

    {"loop": "train_fit", "batch_size": 64, "pool_batches": 8,
     "warmup_steps": 5, "fields": {<name>: <field spec>, ...}}

Field specs (rows of every batch differ; every seed gives the same SHAPES):

- ``{"kind": "tokens", "width": 512, "low": 3, "high": 10003,
   "length_low": 64, "length_high": 512, "pad_id": 0, "mask": "pad_mask"}``
  int32 ids uniform in [low, high), a real length per row uniform in
  [length_low, length_high], ``pad_id`` beyond it, and a bool field named by
  ``mask`` that is True at padding.
- ``{"kind": "normal", "shape": [224, 224, 3], "dtype": "float32"}``
- ``{"kind": "labels", "classes": 1000}`` int32 uniform in [0, classes).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _field(rng: np.random.Generator, rows: int, spec: Dict[str, Any]) -> Dict[str, np.ndarray]:
    kind = spec["kind"]
    if kind == "tokens":
        width = spec["width"]
        ids = rng.integers(spec["low"], spec["high"], size=(rows, width), dtype=np.int32)
        lengths = rng.integers(spec["length_low"], spec["length_high"] + 1, size=rows)
        pad = np.arange(width)[None, :] >= lengths[:, None]
        ids[pad] = spec["pad_id"]
        return {"": ids, spec["mask"]: pad}
    if kind == "normal":
        return {"": rng.standard_normal((rows, *spec["shape"])).astype(spec["dtype"])}
    if kind == "labels":
        return {"": rng.integers(0, spec["classes"], size=rows, dtype=np.int32)}
    raise ValueError(f"unknown traffic field kind {kind!r}")


def make_batches(mix: Dict[str, Any], seed: int) -> List[Dict[str, np.ndarray]]:
    """``pool_batches`` host batches of ``batch_size`` rows, all different."""
    rng = np.random.default_rng([int(seed), 0xBA7C4])
    pool = []
    for _ in range(mix["pool_batches"]):
        batch: Dict[str, np.ndarray] = {}
        for name, spec in mix["fields"].items():
            for suffix, array in _field(rng, mix["batch_size"], spec).items():
                batch[suffix or name] = array
        pool.append(batch)
    return pool


# -- served text ----------------------------------------------------------------


def make_vocabulary(size: int, specials=("[PAD]", "[UNK]", "[MASK]")) -> List[str]:
    """``size`` distinct tokens: the specials first, then whole words of
    lowercase letters (``baaa``, ``baab`` ...), each one WordPiece token."""
    words = list(specials)
    n = 0
    while len(words) < size:
        digits, v = [], n
        for _ in range(4):
            digits.append("abcdefghijklmnopqrstuvwxyz"[v % 26])
            v //= 26
        words.append("w" + "".join(reversed(digits)))
        n += 1
    return words


def lognormal_lengths(count: int, median: float, sigma: float, low: int, high: int) -> np.ndarray:
    """``count`` lengths at the evenly spaced quantiles of a log-normal,
    clipped: the SAME set for every seed (the seed only orders them)."""
    from statistics import NormalDist

    qs = (np.arange(count) + 0.5) / count
    z = np.array([NormalDist().inv_cdf(float(q)) for q in qs])
    return np.clip(np.round(median * np.exp(sigma * z)), low, high).astype(np.int64)


def make_requests(mix: Dict[str, Any], vocab_size: int, seed: int) -> List[Dict[str, Any]]:
    """Fill-mask requests of a serving mix:

        "requests": {"pool": 2048, "length_median": 230, "length_sigma": 0.6,
                     "length_low": 16, "length_high": 512,
                     "masks_low": 1, "masks_high": 4}

    Each is ``{"ids": natural-length token ids with the mask id spliced in,
    "mask_positions": [...], "text": the same as a string}``. Every seed gets
    the same multiset of (length, masks) pairs in another order, and other
    words."""
    spec = mix["requests"]
    rng = np.random.default_rng([int(seed), 0x5E12E])
    vocab = make_vocabulary(vocab_size)
    lengths = lognormal_lengths(spec["pool"], spec["length_median"], spec["length_sigma"],
                                spec["length_low"], spec["length_high"])
    span = spec["masks_high"] - spec["masks_low"] + 1
    masks = spec["masks_low"] + (np.arange(spec["pool"]) * 7919) % span
    order = rng.permutation(spec["pool"])
    out = []
    for i in order:
        n, m = int(lengths[i]), int(masks[i])
        ids = rng.integers(3, vocab_size, size=n)
        positions = np.sort(rng.choice(n, size=m, replace=False))
        ids[positions] = 2
        text = " ".join(vocab[t] for t in ids)
        out.append({"ids": ids.astype(np.int32), "mask_positions": positions, "text": text})
    return out
