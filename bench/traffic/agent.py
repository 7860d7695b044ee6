"""Agent tool loops (BFCL-style function calling, the Continuum setting).

Jobs arrive open-loop, Poisson at ``rate`` jobs/s.  A job's first prompt
is a shared system prompt and a task; it then makes ``calls`` tool calls,
each a scripted model turn followed by a tool run whose result is
appended to the history, and ends with a final answer turn.  The job
announces each tool's duration; the tool really runs that long times
``1 + U(-tool_jitter, tool_jitter)``.  A job ends before its context
would pass ``max_context``.
"""
from __future__ import annotations

import random
from typing import Dict, List

from bench.harness.traffic import (SessionPlan, TurnPlan, arrivals,
                                   lognormal_clipped, seed_rng,
                                   tokens)


def shapes(spec: Dict, n: int) -> List[Dict]:
    rng = random.Random(spec.get("shape_seed", 0) + 1)
    out = []
    for _ in range(n):
        task = lognormal_clipped(rng, spec["task_len"])
        n_calls = rng.randint(*spec["calls"])
        turns = []
        for call in range(n_calls + 1):
            is_tool = call < n_calls
            tool = rng.uniform(*spec["tool_s"]) if is_tool else 0.0
            jit = rng.uniform(-spec["tool_jitter"], spec["tool_jitter"])
            turns.append((lognormal_clipped(rng, spec["output_len"]),
                          lognormal_clipped(rng, spec["tool_result_len"])
                          if is_tool else 0,
                          tool, tool * (1.0 + jit)))
        out.append({"task": task, "turns": turns})
    return out


def generate(spec: Dict, rate: float, seed: int, start: float, end: float,
             vocab: int) -> List[SessionPlan]:
    times = arrivals(spec, rate, start, end)
    shp = shapes(spec, len(times))
    rng = seed_rng(seed, 3)
    system = tokens(rng, spec["system_len"], vocab)
    cap = spec["max_context"]
    plans = []
    for sid, (t, s) in enumerate(zip(times, shp)):
        prompt0 = system + tokens(rng, s["task"], vocab)
        ctx = len(prompt0)
        turns: List[TurnPlan] = []
        for out_len, res_len, tool, actual in s["turns"]:
            if ctx + out_len > cap:
                break
            ctx += out_len + res_len
            turns.append(TurnPlan(output=tokens(rng, out_len, vocab),
                                  after=tokens(rng, res_len, vocab),
                                  tool_s=tool, wait_s=actual))
        last = turns[-1]
        last.after, last.tool_s, last.wait_s = [], 0.0, 0.0
        plans.append(SessionPlan(sid=sid, due=t, prompt0=prompt0,
                                 turns=turns))
    return plans
