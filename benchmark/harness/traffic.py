"""The one general traffic generator. A mix is a data file (``traffic/<name>.json``);
this module turns its parameters and ``--seed`` into rows (training) or requests
(serving). Every seed gives the SAME multiset of lengths and arrival gaps, in another
order: the seed changes the order and the tokens, never the amount of work."""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _quantile_grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _part_counts(shares: list, n: int) -> list:
    """``n`` split by ``shares`` (which sum to 1): each part's floor, and what is left
    over to the largest remainders, the earlier part first among equals."""
    if abs(sum(shares) - 1.0) > 1e-9 or min(shares) < 0:
        raise ValueError(f"a mixture's shares have to be >= 0 and sum to 1, got {shares}")
    exact = [s * n for s in shares]
    counts = [int(x + 1e-9) for x in exact]
    by_remainder = sorted(range(len(shares)), key=lambda i: (counts[i] - exact[i], i))
    for i in by_remainder[: n - sum(counts)]:
        counts[i] += 1
    return counts


def length_set(law: dict, n: int) -> np.ndarray:
    """``n`` lengths: the law's quantiles at (i + 0.5) / n, clipped to its bounds. A
    ``mixture`` gives each of its ``parts`` (a law with a ``share``) its share of ``n``
    and that part's own quantiles and bounds, so that short and long requests sit in one
    queue in fixed numbers."""
    kind = law["law"]
    if kind == "mixture":
        counts = _part_counts([part["share"] for part in law["parts"]], n)
        return np.concatenate([length_set(part, k) for part, k in zip(law["parts"], counts)])
    u = _quantile_grid(n)
    if kind == "fixed":
        return np.full(n, int(law["value"]), np.int64)
    if kind == "uniform":
        x = law["min"] + u * (law["max"] - law["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = law["median"] * np.exp(law["sigma"] * z)
    else:
        raise ValueError(f"unknown length law {kind!r}")
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def length_bounds(law: dict) -> tuple:
    """(shortest, longest) length the law can give."""
    if law["law"] == "mixture":
        bounds = [length_bounds(part) for part in law["parts"]]
        return min(lo for lo, _ in bounds), max(hi for _, hi in bounds)
    if law["law"] == "fixed":
        return int(law["value"]), int(law["value"])
    return law["min"], law["max"]


def arrival_gaps(rate_rps: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process: the exponential law's quantiles."""
    return -np.log1p(-_quantile_grid(n)) / rate_rps


def make_requests(mix: dict, sizes: dict, seed: int, n: int, rate_rps: float | None = None,
                  page_size: int = 1) -> list:
    """``n`` requests of a serving mix: dicts with ``prompt`` (int32 array),
    ``new_tokens`` and, for an open loop, ``due`` (seconds from the start of the load)."""
    rng = np.random.default_rng([seed, 1])
    vocab = sizes["vocab_size"]
    prompt_len = rng.permutation(length_set(mix["prompt_tokens"], n))
    new_tokens = rng.permutation(length_set(mix["new_tokens"], n))
    longest = length_bounds(mix["prompt_tokens"])[1]
    shared = mix.get("shared_prefix")
    behind = np.zeros(n, bool)
    preambles = None
    if shared:
        if shared["preamble_tokens"] % page_size:
            raise ValueError("preambles must be page-aligned")
        behind[: int(round(shared["share"] * n))] = True
        behind = rng.permutation(behind)
        preambles = rng.integers(1, vocab, size=(shared["preambles"], shared["preamble_tokens"]))
    due = None
    if mix["kind"] == "open_loop":
        due = np.cumsum(rng.permutation(arrival_gaps(rate_rps, n)))
    requests = []
    for i in range(n):
        length = int(prompt_len[i])
        if behind[i]:
            pre = preambles[int(rng.integers(0, len(preambles)))]
            length = min(len(pre) + length, longest)
            prompt = np.concatenate([pre, rng.integers(1, vocab, size=length - len(pre))])
        else:
            prompt = rng.integers(1, vocab, size=length)
        requests.append({
            "index": i, "prompt": prompt.astype(np.int32), "new_tokens": int(new_tokens[i]),
            "due": None if due is None else float(due[i]), "behind_preamble": bool(behind[i]),
        })
    return requests


def markov_rows(stream: dict, sizes: dict, seed: int, rows: int, seq_len: int) -> np.ndarray:
    """``rows`` rows of ``seq_len + 1`` tokens of a seeded Markov chain over
    ``stream['states']`` of the vocabulary's ids (all rows differ)."""
    rng = np.random.default_rng([seed, 2])
    states, succ = stream["states"], stream["successors"]
    ids = rng.choice(sizes["vocab_size"], size=states, replace=False)
    nxt = np.stack([rng.choice(states, size=succ, replace=False) for _ in range(states)])
    # successor k of a state is taken with probability ~ 2**-k (renormalised)
    p = 0.5 ** np.arange(succ)
    cdf = np.cumsum(p / p.sum())
    draws = np.searchsorted(cdf, rng.random((rows, seq_len + 1)))
    out = np.empty((rows, seq_len + 1), np.int64)
    state = rng.integers(0, states, size=rows)
    for t in range(seq_len + 1):
        out[:, t] = state
        state = nxt[state, np.minimum(draws[:, t], succ - 1)]
    return ids[out].astype(np.int32)

