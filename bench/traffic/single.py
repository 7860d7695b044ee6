"""Single-turn requests with no common prefix.

Requests arrive open-loop, Poisson at ``rate`` requests/s, each with its
own prompt and scripted output, both lognormal and clipped.  Nothing is
shared and nothing returns, so the prefix cache, the evictor and
multi-segment contexts have nothing to do.
"""
from __future__ import annotations

import random
from typing import Dict, List

from bench.harness.traffic import (SessionPlan, TurnPlan, arrivals,
                                   lognormal_clipped, seed_rng,
                                   tokens)


def generate(spec: Dict, rate: float, seed: int, start: float, end: float,
             vocab: int) -> List[SessionPlan]:
    times = arrivals(spec, rate, start, end)
    base = random.Random(spec.get("shape_seed", 0) + 1)
    shp = [(lognormal_clipped(base, spec["prompt_len"]),
            lognormal_clipped(base, spec["output_len"])) for _ in times]
    rng = seed_rng(seed, 3)
    return [SessionPlan(sid=sid, due=t, prompt0=tokens(rng, p, vocab),
                        turns=[TurnPlan(output=tokens(rng, o, vocab),
                                        after=[])])
            for sid, (t, (p, o)) in enumerate(zip(times, shp))]
