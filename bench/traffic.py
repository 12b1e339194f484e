"""The serving traffic generator, read from a traffic file's parameters.

A copy of the program's ``scenarios/traffic.py`` (``generate_traffic``
and ``prompt_tokens``), kept here so that a change to the program cannot
move the yardstick, plus one option: lengths drawn from a published
distribution.  With ``prompt_median`` a mix gives prompt and output
lengths as log-normal distributions (median, ``*_sigma``); they are dealt
in blocks of ``quantile_block`` requests that each hold the same quantiles,
shuffled by the seed, so every seed serves the same set of lengths in
another order.  A prompt is rounded to the nearest multiple of
``prompt_multiple`` tokens (at least one), the program's bucketing by
length, and prompt and output together stay under ``max_positions``.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

_TRAFFIC_SALT = 0x7AFF1C


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    arrival_step: int
    prompt_len: int
    gen_len: int
    raw_len: int = 0
    session: Optional[int] = None
    hot: bool = False
    prompt_id: int = -1


def _quantile_deal(median: float, sigma: float, block: int, n: int,
                   rng: np.random.Generator) -> List[float]:
    """``n`` draws of a log-normal (median, sigma), dealt in blocks that
    each hold its ``block`` mid-quantiles, in an order shuffled by ``rng``."""
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / block)
         for i in range(block)]
    deck = median * np.exp(sigma * np.asarray(z))
    out: List[float] = []
    while len(out) < n:
        out += [float(x) for x in rng.permutation(deck)]
    return out[:n]


def _dealt_lengths(t: Dict[str, Any], n: int, seed: int):
    """(raw prompt, prompt, output) lengths of ``n`` requests."""
    rng = np.random.default_rng(seed + _TRAFFIC_SALT + 1)
    block, mult = int(t["quantile_block"]), int(t["prompt_multiple"])
    raw = _quantile_deal(t["prompt_median"], t["prompt_sigma"], block, n, rng)
    gen = _quantile_deal(t["output_median"], t["output_sigma"], block, n, rng)
    out = []
    for r, g in zip(raw, gen):
        p = mult * max(1, int(round(r / mult)))
        g = max(1, int(round(g)))
        if p + g >= int(t["max_positions"]):
            raise ValueError(f"a prompt of {p} and {g} tokens out pass "
                             f"{t['max_positions']} positions")
        out.append((max(1, int(round(r))), p, g))
    return out


def generate(t: Dict[str, Any], vocab: int, seed: int) -> List[Request]:
    """``t["n_requests"]`` requests, sorted by (arrival step, rid).  An
    ``arrival_rate`` of null makes every request due at step 0."""
    rng = np.random.default_rng(seed + _TRAFFIC_SALT)
    buckets = list(t.get("length_buckets", [1]))
    mix = np.asarray(t.get("length_mix", [1.0]), dtype=np.float64)
    mix = mix / mix.sum()
    n = int(t["n_requests"])
    rate = t.get("arrival_rate")
    burstiness = float(t.get("burstiness", 0.0))
    hot_fraction = float(t.get("hot_fraction", 0.0))
    hot_bucket = int(t.get("hot_bucket", 0))
    gen_jitter = int(t.get("gen_jitter", 0))
    sessions = int(t.get("sessions", 0))
    dealt = _dealt_lengths(t, n, seed) if "prompt_median" in t else None
    out: List[Request] = []
    clock, step = 0.0, 0
    for rid in range(n):
        gap = rng.exponential(1.0 / max(rate, 1e-9)) if rate else 0.0
        burst = rng.random() < burstiness
        hot = rng.random() < hot_fraction
        b = int(rng.choice(len(buckets), p=mix))
        lo = 1 if b == 0 else buckets[b - 1] + 1
        raw = int(rng.integers(lo, buckets[b] + 1))
        gj = (int(rng.integers(-gen_jitter, gen_jitter + 1))
              if gen_jitter else 0)
        prompt_len, gen_len = buckets[b], max(1, int(t.get("gen_len", 1)) + gj)
        if dealt is not None:
            raw, prompt_len, gen_len = dealt[rid]
        if rid > 0 and not burst:
            clock += gap
            step = int(clock)
        if hot:
            prompt_len = raw = buckets[hot_bucket]
        out.append(Request(
            rid=rid, arrival_step=step, prompt_len=prompt_len, raw_len=raw,
            gen_len=gen_len,
            session=(rid % sessions) if sessions else None, hot=hot,
            prompt_id=(-1 if hot else rid)))
    return sorted(out, key=lambda r: (r.arrival_step, r.rid))


def prompt_tokens(req: Request, vocab: int, seed: int) -> np.ndarray:
    """The request's literal prompt, ``(1, prompt_len)`` int32, from its
    ``prompt_id`` and the run's seed."""
    rng = np.random.default_rng(seed + _TRAFFIC_SALT
                                + 7919 * (req.prompt_id + 2))
    return rng.integers(0, vocab, size=(1, req.prompt_len), dtype=np.int32)
