"""Traffic from a mix file and a seed: session arrivals, scripts and fleet.

Every seed gets the same multiset of session lengths, compressors and
arrival gaps, in another order. The lengths are the quantiles of the mix's
clipped lognormal at (i + 0.5) / S for the S scripts of the pool, and the
arrival gaps are the quantiles of a unit exponential, mapped through the
mix's cumulative rate (a Poisson process, or an MMPP with calm and burst
phases of fixed length). Each multiset is laid out in a low-discrepancy
order (the base-2 radical inverse of the quantile's rank) rotated by an
offset drawn from the seed: any run of consecutive sessions holds nearly
the same spread of lengths, and any stretch of time nearly the same
number of arrivals, as the gap distribution stays exponential. So the
work in a window does not change with the seed; only which session comes
when, and the token ids, do.

Session i replays pool script i mod S. A script is a prompt and an answer
of token ids drawn from the seed; its inputs are fixed, so a session sends
script token t at step t whatever the label owner answers.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Plan:
    t_due: np.ndarray           # (N,) arrival times, seconds after start
    tokens: np.ndarray          # (S, max_len) int32 script token ids
    prompt_len: np.ndarray      # (S,) prompt tokens of each script
    answer_len: np.ndarray      # (S,) generated tokens of each script
    comp: np.ndarray            # (S,) index into `specs`
    specs: List[str]            # compressor specs of the fleet

    def script(self, session: int) -> int:
        return session % len(self.prompt_len)

    def steps(self, script: int) -> int:
        """Frames a session of this script sends: the prompt, then every
        answer token but the last (its reply ends the session)."""
        return int(self.prompt_len[script] + self.answer_len[script] - 1)


def lognormal_set(n: int, median: float, sigma: float, lo: int,
                  hi: int) -> np.ndarray:
    """The n quantiles of a lognormal at (i + 0.5) / n, clipped, rounded."""
    nd = NormalDist()
    q = [median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), lo, hi).astype(np.int64)


def unit_gaps(n: int) -> np.ndarray:
    """The n quantiles of a unit exponential at (i + 0.5) / n."""
    return -np.log1p(-(np.arange(n) + 0.5) / n)


def spread_order(n: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of range(n) that visits ranks in base-2 radical
    inverse order (0, 1/2, 1/4, 3/4, ... of the range), rotated by a
    seeded offset and reflected on a seeded coin."""
    def radical_inverse(i: int) -> float:
        x, f = 0.0, 0.5
        while i:
            x += f * (i & 1)
            i >>= 1
            f /= 2
        return x

    order = np.argsort([radical_inverse(i) for i in range(n)], kind="stable")
    order = np.roll(order, int(rng.integers(n)))
    return order[::-1].copy() if rng.integers(2) else order


def share_counts(shares: List[float], n: int) -> List[int]:
    """Largest-remainder split of n items by the given shares."""
    raw = [s * n / sum(shares) for s in shares]
    counts = [int(r) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def arrival_times(arr: dict, horizon_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival times in [0, horizon_s): unit-exponential quantile gaps in a
    seeded spread order, cumulated, then mapped through the inverse
    cumulative rate."""
    if arr["process"] == "poisson":
        phases = [(horizon_s, arr["sessions_per_s"])]
    elif arr["process"] == "mmpp":
        phases, t = [], 0.0
        while t < horizon_s:
            phases += [(arr["calm_s"], arr["sessions_per_s"]),
                       (arr["burst_s"], arr["burst_per_s"])]
            t += arr["calm_s"] + arr["burst_s"]
    else:
        raise ValueError(f"arrival process {arr['process']!r}")
    total = sum(d * r for d, r in phases)       # expected arrivals
    n = int(math.ceil(total))
    gaps = unit_gaps(n)[spread_order(n, rng)]
    # the n gaps, rescaled to fill the horizon, each ending at an arrival
    # (so all n arrivals fall inside it, for every order)
    u = (np.cumsum(gaps) - gaps) * (total / gaps.sum())
    # invert the piecewise-linear cumulative rate
    edges_t = np.concatenate([[0.0], np.cumsum([d for d, _ in phases])])
    edges_u = np.concatenate([[0.0], np.cumsum([d * r for d, r in phases])])
    t = np.interp(u, edges_u, edges_t)
    return t[t < horizon_s]


def plan(traffic: dict, vocab: int, max_len: int, seed: int,
         horizon_s: float) -> Plan:
    rng = np.random.default_rng([seed, 0x7A11C])
    s = int(traffic["pool_scripts"])
    p = traffic["prompt_tokens"]
    a = traffic["answer_tokens"]
    prompt = lognormal_set(s, p["median"], p["sigma"], p["min"], p["max"])
    answer = lognormal_set(s, a["median"], a["sigma"], a["min"], a["max"])
    if int((prompt + answer).max()) > max_len:
        raise ValueError(f"scripts reach {int((prompt + answer).max())} "
                         f"tokens, over the arena's max_len {max_len}")
    fleet = traffic["compressors"]
    comp = np.repeat(np.arange(len(fleet)),
                     share_counts([c["share"] for c in fleet], s))
    # prompts, answers and compressors are ordered independently, so the
    # pool pairs them differently per seed but holds the same multisets
    prompt, answer, comp = (np.sort(a)[spread_order(s, rng)]
                            for a in (prompt, answer, comp))
    tokens = rng.integers(0, vocab, size=(s, max_len), dtype=np.int32)
    t_due = arrival_times(traffic["arrivals"], horizon_s, rng)
    return Plan(t_due=t_due, tokens=tokens, prompt_len=prompt,
                answer_len=answer, comp=comp,
                specs=[c["spec"] for c in fleet])
