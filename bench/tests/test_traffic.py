"""Traffic generators: one seed gives one script; every seed gets the same
sizes and gaps; contexts stay under their cap; the window cut folds the
history into the first served prompt."""
import json
from collections import Counter
from pathlib import Path

import pytest

from bench.harness import serve as sv
from bench.harness import traffic as tr

BENCH = Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def generate(mix, seed, start=-300.0, end=60.0):
    spec = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    gen = sv.load_module(BENCH / "traffic" / f"{spec['kind']}.py")
    return spec, gen.generate(spec, spec["rate"], seed, start, end, 1000)


def sizes(plans):
    return Counter((len(p.prompt0),
                    tuple((len(t.output), len(t.after), round(t.wait_s, 9))
                          for t in p.turns)) for p in plans)


@pytest.mark.parametrize("mix", MIXES)
def test_one_seed_gives_identical_scripts(mix):
    _, a = generate(mix, 2**31 + 11)
    _, b = generate(mix, 2**31 + 11)
    assert a == b and a


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_sizes_and_arrivals_not_tokens(mix):
    _, a = generate(mix, 7)
    _, b = generate(mix, 8)
    assert sizes(a) == sizes(b)
    assert [p.due for p in a] == [p.due for p in b]
    assert [p.prompt0 for p in a] != [p.prompt0 for p in b]


@pytest.mark.parametrize("mix", MIXES)
def test_contexts_stay_under_cap(mix):
    spec, plans = generate(mix, 5)
    for p in plans:
        ctx = len(p.prompt0)
        for t in p.turns:
            ctx += len(t.output)
            assert ctx <= spec["max_context"]
            ctx += len(t.after)
        assert p.turns[-1].after == []


def test_split_folds_history_into_first_prompt():
    t1 = tr.TurnPlan(output=[5, 6], after=[7], wait_s=10.0)
    t2 = tr.TurnPlan(output=[8], after=[9], wait_s=10.0)
    t3 = tr.TurnPlan(output=[10], after=[])
    p = tr.SessionPlan(sid=3, due=-15.0, prompt0=[1, 2], turns=[t1, t2, t3])
    past, live = tr.split_at_window([p], 0.0)
    assert past == [(-5.0, 3, [1, 2, 5, 6, 7, 8])]
    assert live[0].due == 5.0 and live[0].turns == [t3]
    assert live[0].prompt0 == [1, 2, 5, 6, 7, 8, 9]


def test_warm_histories_keep_most_recent_under_cap():
    past = [(-30.0, 1, [0] * 50), (-10.0, 2, [0] * 40), (-20.0, 3, [0] * 30)]
    assert [s for s, _ in tr.warm_histories(past, 75)] == [3, 2]
