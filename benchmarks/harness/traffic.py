"""The one generator of traffic. A mix is a data file
(`traffic/<name>.json`); its `kind` picks the loop:

- `train`: batches of random token ids.
- `closed_loop`: `clients` callers, each sending its next request when the
  last one finished.
- `open_loop`: requests due at times drawn from `arrivals`, whether or not
  earlier ones finished; lateness of the generator is reported.

Request lengths are a fixed stratified set: the quantiles of the stated
distribution at `requests` evenly spaced points, prompts paired with outputs
through the mix's own fixed permutation. `--seed` permutes the ORDER in which
the set is sent (in a closed loop the order is dealt round-robin into one
sequence per caller) and draws the token ids and the weights. Every seed
offers the same set of work in another order, so two seeds differ by which
requests, and which of their phases, fall inside the window, and the bounds
carry that spread (PERF.md, PR 24).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple

import numpy as np


class RequestSpec(NamedTuple):
    prompt_len: int
    new_tokens: int


def quantile_lengths(dist: Dict[str, Any], n: int) -> List[int]:
    """`n` lengths at the mid-point quantiles (i + 0.5) / n of `dist`:
    {"dist": "uniform" | "log_uniform", "min": a, "max": b}."""
    kind = dist["dist"]
    lo, hi = float(dist["min"]), float(dist["max"])
    qs = (np.arange(n) + 0.5) / n
    if kind == "uniform":
        vals = lo + (hi - lo) * qs
    elif kind == "log_uniform":
        vals = np.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * qs)
    else:
        raise ValueError(f"unknown length distribution '{kind}'")
    return [int(round(v)) for v in vals]


def request_set(traffic: Dict[str, Any], seed: int) -> List[RequestSpec]:
    """The mix's fixed set of (prompt, output) lengths, in the order `seed`
    sends them in. Prompt and output quantiles are paired through a fixed
    permutation from the mix's own `pairing_seed`, the same for every seed,
    so that long prompts do not always carry long answers."""
    n = int(traffic["requests"])
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    outputs = quantile_lengths(traffic["output_tokens"], n)
    pairing = np.random.RandomState(
        int(traffic.get("pairing_seed", 0))).permutation(n)
    pairs = [RequestSpec(prompts[i], outputs[j])
             for i, j in enumerate(pairing)]
    order = np.random.RandomState(seed32(seed, salt=-1)).permutation(n)
    return [pairs[i] for i in order]


def client_sequences(traffic: Dict[str, Any], seed: int
                     ) -> List[List[RequestSpec]]:
    """Closed loop: caller c cycles through its own sequence, the seed's
    order dealt round-robin into `clients` sequences."""
    clients = int(traffic["clients"])
    requests = request_set(traffic, seed)
    return [requests[c::clients] for c in range(clients)]


def seed32(seed: int, salt: int = 0) -> int:
    """`--seed` may be a little over 2**31; RandomState takes 32 bits."""
    return (int(seed) * 2654435761 + salt * 40503 + 12345) % (2 ** 32)


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of the `index`-th request sent: distinct per request, so
    no two prompts share a prefix block except by chance."""
    rng = np.random.RandomState(seed32(seed, index + 1))
    return rng.randint(0, vocab, length).astype(np.int32)


def calibration_ids(seed: int, sequences: int, length: int, vocab: int
                    ) -> np.ndarray:
    """The batch a balanced placement of held experts is counted on
    (`program.place_held_experts`): `sequences` rows of `length` ids, drawn
    from the seed apart from every request's ids and from the order the
    requests are sent in."""
    rng = np.random.RandomState(seed32(seed, salt=-2))
    return rng.randint(0, vocab, (sequences, length)).astype(np.int32)


def train_batches(seed: int, n_batches: int, rows: int, seq: int,
                  vocab: int) -> List[np.ndarray]:
    """`n_batches` host arrays of shape (1, rows, seq): the small set the
    feed cycles through (so the loss must fall inside a window)."""
    rng = np.random.RandomState(seed32(seed))
    return [rng.randint(0, vocab, (1, rows, seq)).astype(np.int32)
            for _ in range(n_batches)]


def arrival_times(arrivals: Dict[str, Any], horizon_s: float) -> List[float]:
    """Open loop: the times, in seconds from the start, at which requests
    are due within `horizon_s`; drawn from the mix's own `arrivals.seed`, so
    every run of the cell sees the same arrivals.
    {"process": "poisson", "rate": r} or {"process": "bursts", "rate": r,
    "burst": b}: groups of `b` requests due together, the groups Poisson at
    rate r / b (the same mean rate)."""
    if arrivals["process"] not in ("poisson", "bursts"):
        raise ValueError(f"unknown arrival process '{arrivals['process']}'")
    rng = np.random.RandomState(int(arrivals.get("seed", 0)))
    rate = float(arrivals["rate"])
    burst = int(arrivals["burst"]) if arrivals["process"] == "bursts" else 1
    times, t = [], 0.0
    while True:
        t += rng.exponential(burst / rate)
        if t >= horizon_s:
            return times
        times.extend([t] * burst)
