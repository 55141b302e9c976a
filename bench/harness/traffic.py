"""One general load generator, driven by a mix's data file.

Every seed gets the same schedule: the lengths are the distribution's
quantiles at ``(i + 1/2) / n`` and the gaps the exponential's, in one
order fixed by ``ORDER_SEED``, with each prompt length paired to an
output length in one fixed way.  The pre-roll and the window each get
such a set of their own.  The seed draws the token ids (and, elsewhere,
the weights): two seeds offer the same requests at the same times, and
differ only in what the tokens are.  An open-loop queue is sensitive to
the order of its arrivals (long prompts early or late), so an order
drawn per seed would make the seed change the work.

A mix (``traffic/<mix>.json``):

  loop          "open": requests fall due on a schedule whatever the
                system does; "closed": ``clients`` callers each send
                their next request when the last one completes.
  rate_per_s    open loop: mean arrivals per second (Poisson gaps).
  preroll_s     optional: seconds of the same traffic offered before the
                window opens, so that the window finds the batch and the
                queue as they stand in steady state rather than empty.
                Its requests are served and their tokens in the window
                count; only requests due in the window are timed.
  clients       closed loop: number of callers.
  prompt/output {"median", "sigma", "min", "max"}: lognormal token
                counts, clipped.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

# the one order in which prompt and output lengths are paired
PAIRING_SEED = 20240611
# the one order of the arrival gaps and of the requests, for every seed
ORDER_SEED = 20250316
# closed-loop callers draw from a pool of this many requests per caller
CLOSED_POOL_PER_CLIENT = 64


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due: float | None           # seconds after the window opens (open loop)
    client: int | None = None   # closed loop


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generators per purpose from one ``--seed`` (any whole
    number; negative ones and ones past 64 bits wrap)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lognormal quantiles at ``(i + 1/2) / n``, rounded, clipped."""
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, mean: float) -> np.ndarray:
    """``n`` exponential quantiles with mean ``mean`` exactly."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (mean / g.mean())


def generate(traffic: dict, seconds: float, seed: int,
             vocab: int) -> list[Request]:
    """The requests of one run: for an open loop those due in the pre-roll
    and the window (sorted by due time, in seconds from the window's
    opening, so pre-roll requests are due before 0), for a closed loop the
    pool callers draw from in order (``due`` None)."""
    order_rng = np.random.default_rng(ORDER_SEED)
    if traffic["loop"] == "open":
        lead = float(traffic.get("preroll_s", 0.0))
        parts = [_open_part(traffic, lead, order_rng) - lead,
                 _open_part(traffic, seconds, order_rng)]
        order = [order_rng.permutation(p.size) for p in parts]
        plen = np.concatenate([_pairs(traffic, p.size)[0][o]
                               for p, o in zip(parts, order)])
        olen = np.concatenate([_pairs(traffic, p.size)[1][o]
                               for p, o in zip(parts, order)])
        due = np.concatenate(parts)
        n = due.size
        clients = [None] * n
    elif traffic["loop"] == "closed":
        n = traffic["clients"] * CLOSED_POOL_PER_CLIENT
        due = [None] * n
        clients = [i % traffic["clients"] for i in range(n)]
        order = order_rng.permutation(n)
        plen, olen = (x[order] for x in _pairs(traffic, n))
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    rng = rng_for(seed, 0)
    prompts = [rng.integers(0, vocab, int(k), dtype=np.int32) for k in plen]
    return [Request(i, prompts[i], int(olen[i]),
                    None if due[i] is None else float(due[i]), clients[i])
            for i in range(n)]


def _pairs(traffic: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` requests' (prompt, output) lengths: the quantiles of each,
    paired in one fixed order that no seed changes."""
    pairing = np.random.default_rng(PAIRING_SEED).permutation(n)
    return (lognormal_quantiles(traffic["prompt"], n),
            lognormal_quantiles(traffic["output"], n)[pairing])


def _open_part(traffic: dict, seconds: float,
               rng: np.random.Generator) -> np.ndarray:
    """Due times, from 0, of one stretch of ``seconds`` of open-loop
    arrivals: the exponential's gap quantiles in ``rng``'s order (none for
    a stretch of no length)."""
    n = int(round(traffic["rate_per_s"] * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = rng.permutation(exponential_gaps(n, seconds / n))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def padded_prompt_lengths(traffic: dict, bucket: int) -> list[int]:
    """Every prompt length, padded up to ``bucket``, the mix can draw."""
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    return list(range(math.ceil(lo / bucket) * bucket,
                      math.ceil(hi / bucket) * bucket + 1, bucket))
