"""One generator for every traffic mix: a mix is a JSON file of parameters
under `bench/traffic/`, and this module turns it and a seed into requests.

The draws are stratified so that the seed changes the order of the work and
not its amount.  Requests come in blocks of `block`; every block holds the
same multiset of prompt lengths, output lengths and inter-arrival gaps (the
distributions' quantiles at the block's midpoints), shuffled by the seed.
Any window of a run therefore sees nearly the same mix whatever the seed.
A mix may split each block's prompts into classes at the lengths listed
under `output_strata`; each class then carries the output distribution's
quantiles for its own count, so that the output tokens of, say, the long
prompts are the same in every block and not a draw from the block's set.

The distributions follow `repro.core.trace` (lognormal prompt body, clipped
to the Azure trace's range) and `repro.core.arrivals` (Poisson, and the
two-state MMPP with quiet and burst sojourns); they are copied here so that
a change to the program cannot move the benchmark's yardstick.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_Z = NormalDist()


@dataclass
class BenchRequest:
    rid: int
    arrival: float          # virtual seconds
    prompt_len: int
    max_new: int
    tokens: np.ndarray      # (prompt_len,) int32


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_block(spec: Dict, n: int) -> np.ndarray:
    """`n` lognormal quantiles (median, sigma), clipped to [min, max]."""
    z = np.array([_Z.inv_cdf(u) for u in _mid_quantiles(n)])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.ceil(vals), spec["min"], spec["max"]).astype(np.int64)


def round_up(vals: np.ndarray, lengths: List[int]) -> np.ndarray:
    """Round each value up to the next entry of the sorted list `lengths`
    (the last entry caps it), so set-up warms a fixed set of shapes."""
    grid = np.asarray(sorted(lengths))
    idx = np.minimum(np.searchsorted(grid, vals, side="left"), len(grid) - 1)
    return grid[idx]


def class_outputs(spec: Dict, ins: np.ndarray, cuts: List[int],
                  rng: np.random.Generator) -> np.ndarray:
    """Output lengths for one block of prompt lengths `ins`: the prompts
    fall into classes at the lengths in `cuts` (a prompt at or over a cut
    is in the class above it), and each class gets the output
    distribution's quantiles for its own count, shuffled among its
    members."""
    cls = np.searchsorted(np.asarray(sorted(cuts)), ins, side="right")
    out = np.empty(len(ins), np.int64)
    for c in np.unique(cls):
        idx = np.flatnonzero(cls == c)
        out[idx] = rng.permutation(lognormal_block(spec, len(idx)))
    return out


def exp_gaps_block(rate: float, n: int) -> np.ndarray:
    """`n` exponential quantiles of mean 1/rate: a Poisson process's gaps."""
    return -np.log1p(-_mid_quantiles(n)) / rate


def arrivals(spec: Dict, rate: float, n: int, block: int,
             rng: np.random.Generator) -> np.ndarray:
    """`n` arrival times at long-run mean `rate` (requests per virtual s)."""
    proc = spec.get("process", "poisson")
    if proc == "poisson":
        gaps = np.concatenate([rng.permutation(exp_gaps_block(rate, block))
                               for _ in range(-(-n // block))])[:n]
        return np.cumsum(gaps)
    if proc == "mmpp":
        # two states with exponential sojourns (quantiles, shuffled); the
        # quiet rate solves (1 - f) r0 + f B r0 = rate as in core/arrivals
        f, B = spec["burst_frac"], spec["burst_factor"]
        cyc = spec["mean_cycle"]
        r_quiet = rate / ((1.0 - f) + f * B)
        k = int(spec["sojourn_block"])
        soj_q = -np.log1p(-_mid_quantiles(k)) * cyc * (1.0 - f)
        soj_b = -np.log1p(-_mid_quantiles(k)) * cyc * f
        out: List[float] = []
        t = 0.0
        while len(out) < n:
            for q, b in zip(rng.permutation(soj_q), rng.permutation(soj_b)):
                for dur, r in ((q, r_quiet), (b, B * r_quiet)):
                    # a Poisson stream at rate r inside the sojourn, its
                    # gaps the stratified exponential quantiles
                    m = max(int(round(r * dur)), 0)
                    if m:
                        g = rng.permutation(exp_gaps_block(r, m))
                        pts = t + np.cumsum(g) * (dur / g.sum())
                        out.extend(pts.tolist())
                    t += dur
                if len(out) >= n:
                    break
        return np.asarray(out[:n])
    raise ValueError(f"unknown arrival process {proc!r}")


def generate(mix: Dict, *, rate: float, n: int, vocab: int,
             seed: int) -> List[BenchRequest]:
    """`n` requests of the mix at `rate`, fixed by `seed`."""
    rng = np.random.default_rng(seed)
    block = int(mix["block"])
    nb = -(-n // block)
    ins = round_up(lognormal_block(mix["input"], block), mix["lengths"])
    outs = lognormal_block(mix["output"], block)
    ins_blocks = [rng.permutation(ins) for _ in range(nb)]
    cuts = mix.get("output_strata")
    outs_all = np.concatenate([
        class_outputs(mix["output"], b, cuts, rng) if cuts
        else rng.permutation(outs) for b in ins_blocks])[:n]
    ins_all = np.concatenate(ins_blocks)[:n]
    arr = arrivals(mix["arrival"], rate, n, block, rng)
    return [BenchRequest(rid=i, arrival=float(arr[i]),
                         prompt_len=int(ins_all[i]), max_new=int(outs_all[i]),
                         tokens=rng.integers(0, vocab, int(ins_all[i]),
                                             dtype=np.int32))
            for i in range(n)]


def used_lengths(mix: Dict) -> List[int]:
    """The prompt lengths this mix can send: what set-up warms."""
    block = int(mix["block"])
    return sorted(set(int(x) for x in round_up(
        lognormal_block(mix["input"], block), mix["lengths"])))


def summary(reqs: List[BenchRequest], long_threshold: int) -> Dict:
    ins = np.array([r.prompt_len for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    span = reqs[-1].arrival - reqs[0].arrival if len(reqs) > 1 else math.nan
    return {"n": len(reqs),
            "long_share": float((ins >= long_threshold).mean()),
            "input_median": float(np.median(ins)),
            "output_median": float(np.median(outs)),
            "rate": (len(reqs) - 1) / span if span > 0 else math.nan}
