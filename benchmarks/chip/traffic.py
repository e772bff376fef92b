"""Training traffic from a seed: document lengths from a heavy-tailed law,
tokens from a sparse-successor Markov chain.

The chain is the one of ``repro.data.synthetic.MarkovLM`` (a successor
table of ``branching`` next tokens per token, drawn with fixed
probabilities), copied here so that the yardstick does not move with the
program.  It runs as many independent chains side by side and cuts the
documents out of their concatenated stream, so a corpus of millions of
tokens takes a few thousand vectorized steps.

Every seed gets the same document lengths in the same order: both come from
the traffic file's own ``layout_seed`` (the dataset's epoch order is keyed
by it too), so the rows, their documents and the attention work are the
same for every seed.  ``--seed`` changes the tokens and the weights, never
the amount of work.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

CHAINS = 4096


def doc_lengths(law: Dict, total_tokens: int, layout_seed: int) -> np.ndarray:
    """Trained lengths (tokens per document) until they sum to at least
    ``total_tokens``.  ``law``: {"law": "lognormal", "median", "sigma",
    "min", "max"}."""
    if law["law"] != "lognormal":
        raise ValueError(f"unknown document-length law {law['law']!r}")
    rng = np.random.default_rng(int(layout_seed))
    mu, sigma = np.log(float(law["median"])), float(law["sigma"])
    lo, hi = int(law["min"]), int(law["max"])
    out, total = [], 0
    while total < total_tokens:
        n = np.clip(np.rint(rng.lognormal(mu, sigma, size=4096)), lo, hi).astype(np.int64)
        out.append(n)
        total += int(n.sum())
    lens = np.concatenate(out)
    return lens[: int(np.searchsorted(np.cumsum(lens), total_tokens)) + 1]


def markov_stream(vocab: int, n_tokens: int, seed: int, branching: int, probs) -> np.ndarray:
    """``n_tokens`` tokens of CHAINS parallel Markov chains, chain by chain."""
    table_rng = np.random.default_rng([int(seed), 0])
    succ = table_rng.integers(0, vocab, size=(vocab, branching))
    cum = np.cumsum(np.asarray(probs, np.float64))
    rng = np.random.default_rng([int(seed), 1])
    steps = -(-n_tokens // CHAINS)
    toks = np.empty((CHAINS, steps), np.int64)
    state = rng.integers(0, vocab, size=CHAINS)
    for t in range(steps):
        toks[:, t] = state
        bucket = np.minimum(np.searchsorted(cum, rng.random(CHAINS)), branching - 1)
        state = succ[state, bucket]
    return toks.reshape(-1)[:n_tokens]


def corpus(traffic: Dict, vocab: int, seed: int) -> Iterator[np.ndarray]:
    """The documents of one run, each stored with its trailing next-token
    target (a stored document of n + 1 tokens trains n pairs), for
    ``repro.data.write_token_cache``."""
    lens = doc_lengths(traffic["docs"], int(traffic["corpus_tokens"]), traffic["layout_seed"])
    stored = lens + 1
    stream = markov_stream(vocab, int(stored.sum()), seed, traffic["markov"]["branching"],
                           traffic["markov"]["probs"])
    ends = np.cumsum(stored)
    for lo, hi in zip(np.concatenate([[0], ends[:-1]]), ends):
        yield stream[lo:hi]
