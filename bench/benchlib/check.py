"""The comparison that decides `correct`.

Once the window has closed, a sample of the requests it finished is drawn
from the seed, the request with the longest prompt always in it, until it
holds `min_tokens` served tokens (or `max_requests` requests).  The plain
reference runs once over each prompt followed by its served tokens.  At
every position where the program served a token, the gap is the
reference's best logit less the reference's logit of the served token, in
units of the standard deviation of the reference's logits there.  The
number compared is the widest gap of the sample.  Greedy decoding serves
the top token of its own logits, so a served path that agrees with the
reference to rounding reads a small gap, and one that computes something
else serves tokens the reference ranks low.

The control puts the reference, computed with float8 weights, in the
program's place: at the same positions it reads the gap of the token that
the lower precision ranks first, and that gap meets the same checks.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def sample(finished: List[int], prompt_len: Dict[int, int],
           served: Dict[int, List[int]], seed: int, min_tokens: int,
           max_requests: int) -> List[int]:
    if not finished:
        return []
    rng = np.random.default_rng(seed ^ 0x5EED)
    longest = max(finished, key=lambda r: (prompt_len[r], r))
    rest = [r for r in sorted(finished) if r != longest]
    out = [longest]
    for r in rng.permutation(rest):
        if (len(out) >= max_requests
                or sum(len(served[x]) for x in out) >= min_tokens):
            break
        out.append(int(r))
    return out


#: how each number compared must stand to its limit
RELATION = {"served_gap_max": "<=", "failed": "<=", "window_compiles": "<=",
            "compared_requests": ">="}


def verdict(checks: Dict[str, List]) -> bool:
    """`correct`: every number compared, `[value, limit]`, within its limit."""
    return all(v >= lim if RELATION[k] == ">=" else v <= lim
               for k, (v, lim) in checks.items())


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: (best reference logit - reference logit of `tokens`)
    / reference logits' standard deviation."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], -1)[:, 0]
    return (best - got) / ref_logits.std(-1)


def compare(ref, cfg: dict, params, prompts: Dict[int, np.ndarray],
            served: Dict[int, List[int]], rids: List[int],
            control: bool = False) -> Dict:
    """Widest gap of the served tokens over `rids` and, with `control`, of
    the float8 reference's top tokens at the same positions."""
    worst = ctl_worst = 0.0
    n_tok = 0
    for rid in rids:
        toks = np.asarray(served[rid], np.int32)
        S, n = len(prompts[rid]), len(toks)
        seq = np.concatenate([prompts[rid], toks[:-1]])
        rows = np.arange(S - 1, S - 1 + n)
        r = ref.logits(cfg, params, seq, rows)
        worst = max(worst, float(gaps(r, toks).max()))
        if control:
            c = ref.logits(cfg, params, seq, rows, weights="fp8")
            ctl_worst = max(ctl_worst,
                            float(gaps(r, c.argmax(-1).astype(np.int32))
                                  .max()))
        n_tok += n
    out = {"served_gap_max": worst, "n_requests": len(rids),
           "n_tokens": n_tok}
    if control:
        out["control_gap_max"] = ctl_worst
    return out
