"""Multi-turn chat over a long document (LooGLE-style QA).

Sessions arrive open-loop, Poisson at ``rate`` sessions/s.  A session's
first prompt is a shared system prompt, a document and a question; every
later prompt is the whole history plus a follow-up.  Answers are
scripted.  The user thinks between turns for an exponential time of mean
``intra_ratio / rate`` (the inter:intra arrival-rate ratio of the
multi-turn workloads), which the server is not told about.  A session
ends before its context would pass ``max_context``.
"""
from __future__ import annotations

import random
from typing import Dict, List

from bench.harness.traffic import (SessionPlan, TurnPlan, arrivals,
                                   lognormal_clipped, seed_rng,
                                   tokens)


def shapes(spec: Dict, rate: float, n: int) -> List[Dict]:
    """``n`` session shapes from the fixed shape seed: lengths and think
    times only, no tokens."""
    rng = random.Random(spec.get("shape_seed", 0) + 1)
    out = []
    for _ in range(n):
        doc = lognormal_clipped(rng, spec["doc_len"])
        n_turns = rng.randint(*spec["turns"])
        turns = [(lognormal_clipped(rng, spec["user_len"]),
                  lognormal_clipped(rng, spec["answer_len"]),
                  rng.expovariate(rate / spec["intra_ratio"]))
                 for _ in range(n_turns)]
        out.append({"doc": doc, "turns": turns})
    return out


def generate(spec: Dict, rate: float, seed: int, start: float, end: float,
             vocab: int) -> List[SessionPlan]:
    times = arrivals(spec, rate, start, end)
    shp = shapes(spec, rate, len(times))
    rng = seed_rng(seed, 3)
    system = tokens(rng, spec["system_len"], vocab)
    cap = spec["max_context"]
    plans = []
    for sid, (t, s) in enumerate(zip(times, shp)):
        user0, _, _ = s["turns"][0]
        prompt0 = system + tokens(rng, s["doc"], vocab) \
            + tokens(rng, user0, vocab)
        ctx = len(prompt0)
        turns: List[TurnPlan] = []
        for i, (_, answer, think) in enumerate(s["turns"]):
            if ctx + answer > cap:
                break
            ctx += answer
            nxt = s["turns"][i + 1][0] if i + 1 < len(s["turns"]) else 0
            turns.append(TurnPlan(output=tokens(rng, answer, vocab),
                                  after=tokens(rng, nxt, vocab),
                                  tool_s=0.0, wait_s=think))
            ctx += nxt
        turns[-1].after = []
        plans.append(SessionPlan(sid=sid, due=t, prompt0=prompt0,
                                 turns=turns))
    return plans
