"""The one traffic generator: reads a mix's parameter file and a cell's
load file, and turns them into requests.

Every seed gets the same requests (sizes and inter-arrival gaps), drawn
once from the mix's own ``multiset_seed`` and kept in one cyclic order
that every run starts at its beginning; ``--seed`` picks the prompt token
ids and the sampled lanes' draws. So two seeds offer the same work in the
same rhythm.

Two kinds of mix:

- ``open``: requests fall due on a schedule, whatever the server does.
  The window holds ``round(rate * seconds)`` requests whose gaps add up
  to the window's length exactly; after the window the cycle goes on,
  keeping the load on while the window's requests finish.
- ``closed``: ``clients`` callers each send their next request the moment
  the previous one comes back, drawing from a pool of ``pool_size``
  requests in order.

Nothing here imports JAX.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    prompt_len: int
    max_new: int
    sampled: bool
    due: float = 0.0  # seconds after the window opens (open mixes)


def _clipped_lognormal(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def multiset(mix: dict, n: int) -> List[tuple]:
    """The mix's ``n`` (prompt_len, max_new, sampled) triples, the same for
    every run seed."""
    rng = np.random.default_rng(mix["multiset_seed"])
    prompts = _clipped_lognormal(rng, mix["prompt"], n)
    outs = _clipped_lognormal(rng, mix["output"], n)
    n_sampled = int(round(mix.get("sampled_share", 0.0) * n))
    sampled = np.zeros(n, bool)
    sampled[rng.permutation(n)[:n_sampled]] = True
    return list(zip(prompts.tolist(), outs.tolist(), sampled.tolist()))


def gaps(mix: dict, n: int, seconds: float) -> np.ndarray:
    """``n`` inter-arrival gaps from the mix's distribution, scaled so that
    they add up to ``seconds``."""
    spec = mix["arrivals"]
    rng = np.random.default_rng([mix["multiset_seed"], 1])
    if spec["dist"] == "gamma":
        g = rng.gamma(spec["shape"], 1.0, n)
    elif spec["dist"] == "poisson":
        g = rng.exponential(1.0, n)
    else:
        raise ValueError(f"unknown arrival distribution {spec['dist']!r}")
    return g * (seconds / g.sum())


def open_requests(mix: dict, load: dict, seconds: float) -> Iterator[Req]:
    """Endless open-loop schedule with a period of one window: the mix's
    ``n`` requests, each paired with a gap, repeat in a fixed cycle that
    starts when the window opens. The window holds few requests (tens),
    and where its bursts fall decides its tail, so every seed gets the
    same schedule; the seed changes the tokens and the weights."""
    n = max(1, int(round(load["rate_per_s"] * seconds)))
    sizes, g = multiset(mix, n), gaps(mix, n, seconds)
    for rid in range(1 << 40):
        if rid % n == 0:
            t = rid // n * seconds  # the gaps add up to one window
        p, o, s = sizes[rid % n]
        yield Req(rid, p, o, s, due=float(t))
        t += g[rid % n]


def closed_requests(mix: dict) -> Iterator[Req]:
    """Endless closed-loop request order: the pool in a fixed cycle from
    its start. A window serves tens of the pool's requests, so a start
    that moved with the seed would change the window's work."""
    n = mix["pool_size"]
    sizes = multiset(mix, n)
    for rid in range(1 << 40):
        p, o, s = sizes[rid % n]
        yield Req(rid, p, o, s)


def prompt_tokens(seed: int, rid: int, n: int, vocab: int) -> np.ndarray:
    """Uniform token ids, so that no two prompts share a prefix."""
    rng = np.random.default_rng([seed, rid, 11])
    return rng.integers(0, vocab, n).astype(np.int32)


def sample_seed(seed: int, rid: int) -> int:
    return int(np.random.default_rng([seed, rid, 13]).integers(0, 2**31 - 1))


def warm_lengths(mix: dict, chunk: int, page: int) -> List[int]:
    """One prompt length per prefill shape the mix can reach: the
    power-of-two buckets up to ``chunk`` and every ``chunk`` multiple past
    it (the engine pads a longer prompt up to the next one)."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out = set()
    b = max(16, page)
    while b <= chunk:  # bucket b serves lengths in (b/2, b]
        if lo <= b and hi > b // 2:
            out.add(min(b, hi))
        b *= 2
    k = 2
    while (k - 1) * chunk < hi:  # k * chunk serves ((k-1) chunk, k chunk]
        if lo <= k * chunk:
            out.add(min(k * chunk, hi))
        k += 1
    return sorted(out)


def check_sample(done: List, seed: int, spec: dict) -> List:
    """The finished requests whose tokens the reference checks, greedy and
    sampled lanes alike: for each lane its longest request (prompt plus
    output), then others in an order drawn from the seed, until the lane
    has ``min_tokens`` served tokens or ``max_requests`` requests."""
    out = []
    for lane in (False, True):
        reqs = [r for r in done if r.sampled == lane]
        if not reqs:
            continue
        longest = max(reqs, key=lambda r: (r.prompt_len + r.max_new, r.rid))
        rest = [r for r in reqs if r is not longest]
        order = np.random.default_rng([seed, 17, lane]).permutation(len(rest))
        out.append(longest)
        n, tokens = 1, longest.max_new
        for i in order:
            if tokens >= spec["min_tokens"] or n >= spec["max_requests"]:
                break
            out.append(rest[i])
            n, tokens = n + 1, tokens + rest[i].max_new
    return out
