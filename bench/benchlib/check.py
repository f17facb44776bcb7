"""The comparison that decides ``correct``.

Once the window has closed and the engine's state is freed, a sample of the
requests it finished, drawn from the seed and holding the one with the most
served tokens, goes through the plain float32 reference: each prompt with
its served tokens, padded to the deployment's ``max_len`` (causal, so the
padding changes nothing before it).  For every served token the reference
gives the gap by which the token's logit lies below the reference's best at
that position, in units of the logits' standard deviation there.  The
numbers compared are statistics of those gaps over the sample (``STATS``);
each has its limit in the cell file.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

STATS = {
    "gap_max": lambda g: float(np.max(g)),
    "gap_mean": lambda g: float(np.mean(g)),
    "gap_p99": lambda g: float(np.percentile(g, 99)),
    "mismatch_share": lambda g: float(np.mean(g > 0)),
}


def sample(recs: Sequence, seed: int, min_tokens: int) -> List:
    """Finished requests: the one with the most served tokens, then others
    in an order drawn from the seed until ``min_tokens`` served tokens are
    held."""
    done = [r for r in recs if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.req.max_new, -r.req.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    out, n = [longest], longest.req.max_new
    for i in rng.permutation(len(rest)):
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += rest[i].req.max_new
    return out


def sequence(prompt: np.ndarray, served: Sequence[int], length: int):
    """Model input (prompt + served tokens but the last, padded to
    ``length``), targets at each position, and the positions of the served
    tokens' predictions."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} exceeds {length}")
    toks = np.zeros((length,), np.int32)
    toks[: len(seq)] = seq
    tgts = np.zeros((length,), np.int32)
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    tgts[pos] = np.asarray(served, np.int32)
    return toks, tgts, pos


def gaps(recs: Sequence, run_ref: Callable, length: int, served_of: Callable
         ) -> np.ndarray:
    """Reference gap of every served token of ``recs``; ``run_ref(tokens,
    targets) -> gaps [length]``; ``served_of(rec)`` the request's served
    tokens."""
    out = []
    for r in recs:
        toks, tgts, pos = sequence(r.req.prompt, served_of(r), length)
        g = np.asarray(run_ref(toks, tgts))
        out.append(g[pos])
    return np.concatenate(out) if out else np.zeros((0,))


def judge(g: np.ndarray, limits: Dict[str, float]) -> Dict[str, Dict]:
    """Each limited statistic beside its limit."""
    return {k: {"value": STATS[k](g), "limit": float(v)} for k, v in limits.items()}


def passed(compared: Dict[str, Dict]) -> bool:
    return bool(compared) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values())
