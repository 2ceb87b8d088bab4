"""The one traffic generator. A traffic file is data; this reads it.

Stratified by construction: a length or gap list is the N quantile
midpoints of its distribution, computed once from the file's parameters,
and ``--seed`` only permutes it and draws the token ids. Every seed
therefore offers the same multiset of prompt lengths, answer lengths and
gaps, in another order.

Serving schedules are periodic: one *cycle* is the complete stratified
set that is due inside a window of ``seconds``; the warm-up before the
window replays the end of the same cycle (same lengths, fresh ids), so
the requests that *finish* inside the window, which arrived a
residence time earlier, are again one complete cycle whatever the
permutation, up to the two edges.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_list(spec: dict, n: int) -> np.ndarray:
    """The n quantile midpoints of a length distribution, as ints,
    clipped to [min, max]."""
    u = midpoints(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def gap_list(n: int, total_s: float) -> np.ndarray:
    """The n quantile midpoints of an exponential, scaled so that one
    cycle lasts exactly ``total_s``."""
    g = -np.log1p(-midpoints(n))
    return g * (total_s / g.sum())


@dataclasses.dataclass
class Request:
    due_s: float  # against the window's opening (negative: warm-up)
    tokens: np.ndarray  # the whole prompt
    max_new: int
    shared: int = 0  # leading tokens shared with the group's other asks


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def serve_schedule(traffic: dict, vocab: int, seed: int, seconds: float) -> list[Request]:
    """Warm-up tail + the window's cycle (+ spare cycles where the whole
    schedule is due at once), due times against the window's opening."""
    arr = traffic["arrivals"]
    share = traffic.get("shared_prefix")
    asks = share["asks"] if share else 1
    groups = max(1, round(arr["rate_per_s"] * seconds / asks))
    n = groups * asks
    own = length_list(traffic["prompt"], n)
    ans = length_list(traffic["answer"], n)
    own = own[_rng(seed, 1).permutation(n)]
    ans = ans[_rng(seed, 2).permutation(n)]
    pre = (
        length_list(share, groups)[_rng(seed, 3).permutation(groups)]
        if share else np.zeros(groups, np.int64)
    )
    if arr["process"] == "exponential_midpoints":
        gaps = gap_list(n, seconds)[_rng(seed, 4).permutation(n)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        cycles_after = 0
    elif arr["process"] == "all_at_start":
        # Above the knee: everything is due before the warm-up starts,
        # in this order; spare cycles keep the queue from emptying.
        due = np.full(n, -math.inf)
        cycles_after = arr["spare_cycles"]
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    ids = _rng(seed, 5)
    out: list[Request] = []

    def emit(i: int, shift: float, prefix: np.ndarray | None) -> None:
        body = ids.integers(0, vocab, int(own[i]), dtype=np.int32)
        toks = body if prefix is None else np.concatenate([prefix, body])
        out.append(Request(float(due[i] + shift), toks, int(ans[i]),
                           0 if prefix is None else int(prefix.size)))

    def cycle(shift: float, first_group: int = 0) -> None:
        for g in range(first_group, groups):
            prefix = (
                ids.integers(0, vocab, int(pre[g]), dtype=np.int32)
                if share else None
            )
            for i in range(g * asks, (g + 1) * asks):
                emit(i, shift, prefix)

    warm = float(arr["warmup_s"])
    if math.isinf(due[0]):
        warm_groups = min(groups, max(1, round(groups * warm / seconds)))
        cycle(0.0, groups - warm_groups)
        for _ in range(1 + cycles_after):
            cycle(0.0)
        for r in out:
            r.due_s = -warm
    else:
        for back in range(math.ceil(warm / seconds), 0, -1):
            cycle(-back * seconds)
        out[:] = [r for r in out if r.due_s >= -warm]
        cycle(0.0)
    return out


def train_rows(traffic: dict, vocab: int, seed: int, rows: int) -> np.ndarray:
    """[rows, seq_len] int32 ids, uniform over the vocabulary."""
    return _rng(seed, 6).integers(
        0, vocab, (rows, traffic["seq_len"]), dtype=np.int32)
