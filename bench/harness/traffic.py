"""Traffic plans: what a generator returns and how the harness cuts it.

A generator (``bench/traffic/<kind>.py``) turns a traffic file's
parameters and a seed into a list of :class:`SessionPlan`: when the
session's first turn is due (seconds relative to the window's start,
negative for sessions that began before it), the tokens of its first
prompt, and its turns.  A turn carries its scripted output, the tokens
appended to the history after it (the next user message or the tool
result), the tool time it announces and the time until the next turn is
due.

Every seed gets the same sizes and arrival gaps, in the same order
(drawn from the traffic file's fixed ``shape_seed``); the run's seed
draws the token ids (and the benchmark's weights).  So the work of a run
does not depend on the seed, only its content does: the program's
schedule does not look at token values, so two seeds differ by host
timing alone.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from typing import Dict, List, Tuple


@dataclass
class TurnPlan:
    output: List[int]
    after: List[int]            # appended to the history after the output
    tool_s: float = 0.0         # announced tool time (0: not announced)
    wait_s: float = 0.0         # until the next turn is due (think/tool)


@dataclass
class SessionPlan:
    sid: int
    due: float                  # first turn due, seconds from window start
    prompt0: List[int]
    turns: List[TurnPlan] = field(default_factory=list)

    def turn_due_times(self) -> List[float]:
        """Nominal due time of every turn, taking each turn to finish at
        once (used only to cut the session at the window's start)."""
        out, t = [], self.due
        for turn in self.turns:
            out.append(t)
            t += turn.wait_s
        return out


def lognormal_clipped(rng: random.Random, spec: Dict) -> int:
    """Integer length from a lognormal with the given median and sigma,
    clipped to [min, max]."""
    x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    return int(min(max(round(x), spec["min"]), spec["max"]))


def poisson_gaps(rng: random.Random, rate: float, horizon: float
                 ) -> List[float]:
    """Exponential inter-arrival gaps whose sum first exceeds horizon."""
    gaps, t = [], 0.0
    while t < horizon:
        g = rng.expovariate(rate)
        gaps.append(g)
        t += g
    return gaps


def tokens(rng: random.Random, n: int, vocab: int) -> List[int]:
    return [rng.randrange(2, vocab) for _ in range(n)]


def seed_rng(seed: int, stream: int) -> random.Random:
    """Independent stream ``stream`` of the run seed (any size of int)."""
    return random.Random(f"{int(seed)}:{stream}")


def arrivals(spec: Dict, rate: float, start: float, end: float
             ) -> List[float]:
    """Session arrival times in [start, end): ``burst`` sessions at
    ``start`` (a backlog standing when serving begins), then Poisson
    gaps at ``rate`` drawn from ``shape_seed``."""
    base = random.Random(spec.get("shape_seed", 0))
    gaps = poisson_gaps(base, rate, end - start)
    out, t = [start] * int(spec.get("burst", 0)), start
    for g in gaps:
        t += g
        if t >= end:
            break
        out.append(t)
    return out


def split_at_window(plans: List[SessionPlan], start: float
                    ) -> Tuple[List[Tuple[float, int, List[int]]],
                               List[SessionPlan]]:
    """Cut every session at ``start`` (the first served instant).

    Turns nominally due before ``start`` are history: their prompt and
    output tokens are prefilled during warm-up.  Returns the histories
    (last past activity, sid, tokens) of every session with past turns,
    and the sessions still to be served from their first turn due at or
    after ``start``, with the history folded into the first prompt."""
    past: List[Tuple[float, int, List[int]]] = []
    live: List[SessionPlan] = []
    for p in plans:
        dues = p.turn_due_times()
        k = sum(1 for d in dues if d < start)
        if k == 0:
            live.append(p)
            continue
        hist = list(p.prompt0)
        for turn in p.turns[:k - 1]:
            hist += turn.output + turn.after
        hist += p.turns[k - 1].output
        past.append((dues[k - 1], p.sid, hist))
        if k < len(p.turns):
            rest = SessionPlan(sid=p.sid, due=dues[k],
                               prompt0=hist + p.turns[k - 1].after,
                               turns=p.turns[k:])
            live.append(rest)
    return past, live


def warm_histories(past: List[Tuple[float, int, List[int]]],
                   cap_tokens: int) -> List[Tuple[int, List[int]]]:
    """The most recent histories whose tokens add up to at most
    ``cap_tokens``, as (sid, tokens), oldest first (the order they would
    have been served in, so the evictor sees the same recency order)."""
    chosen, total = [], 0
    for when, sid, hist in sorted(past, key=lambda x: -x[0]):
        if total + len(hist) > cap_tokens:
            continue
        chosen.append((when, sid, hist))
        total += len(hist)
    return [(sid, h) for _, sid, h in sorted(chosen, key=lambda x: x[0])]
