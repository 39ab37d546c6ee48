"""Seeded token corpus, made shard by shard.

Shard ``i`` of seed ``s`` holds exactly ``shard_tokens`` uniform token ids,
drawn from ``(s, i)``, cut into documents of lognormal length (median,
sigma, cap): the same sequence of lengths in every shard.  Any shard can be
made again from ``(s, i)`` alone,
so a backlog never runs dry, host memory does not grow with the window, and
the reference regenerates what the program was given.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def _rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFFFFFFFFFF, int(index)]))


def doc_lengths(corpus: Dict[str, Any]) -> np.ndarray:
    """The document lengths every shard is cut into, in order: evenly spaced
    quantiles of the lognormal (median, sigma), capped, the longest
    shortened so that they add up to exactly ``shard_tokens``, in one fixed
    shuffled order.  Every shard of every seed holds the same sequence of
    lengths, so every shard packs into the same rows: a seed changes the
    tokens, and the order in which the feeder reads blocks, never the amount
    of work."""
    n_tok = int(corpus["shard_tokens"])
    median, sigma = float(corpus["doc_len_median"]), float(corpus["doc_len_sigma"])
    cap = int(corpus["doc_len_cap"])
    from statistics import NormalDist
    mean = median * np.exp(sigma ** 2 / 2)
    n = max(1, int(round(n_tok / mean)))
    while True:
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        lens = np.clip(np.round(median * np.exp(sigma * z)), 1, cap).astype(np.int64)
        if lens.sum() >= n_tok:
            break
        n += 1
    while lens.sum() - lens[-1] >= n_tok:      # too many: drop the longest
        lens = lens[:-1]
    lens[-1] -= lens.sum() - n_tok
    return np.random.default_rng(0x5EED).permutation(lens)


def shard_docs(seed: int, index: int, corpus: Dict[str, Any]) -> List[np.ndarray]:
    """The documents of one shard, in order (views of one int32 buffer)."""
    rng = _rng(seed, index)
    n = int(corpus["shard_tokens"])
    flat = rng.integers(0, int(corpus["vocab_size"]), n, dtype=np.int32)
    lens = doc_lengths(corpus)
    ends = np.cumsum(lens)
    return [flat[a:b] for a, b in zip(ends - lens, ends)]


def shard_item(seed: int, index: int, corpus: Dict[str, Any]):
    """Shard ``index`` as the program's ingest item: one file arrival,
    labelled with its shard index so its blocks can be found again."""
    from repro.core.items import Granularity, IngestItem, Label

    docs = shard_docs(seed, index, corpus)
    col = np.empty(len(docs), object)
    for i, d in enumerate(docs):
        col[i] = d
    cols = {"tokens": col,
            "length": np.array([len(d) for d in docs], np.int32),
            "doc_id": np.arange(len(docs), dtype=np.int64)}
    return IngestItem(cols, Granularity.FILE, (Label("shard", int(index)),))
