"""Traffic generators: the same plan for the same seed, the same set of
sizes for every seed, and length distributions as the mix states."""
import json
import statistics
from pathlib import Path

import pytest

from chipbench.traffic import generator as gen

MIX = gen.load("long_batch")


def plan(seed):
    return gen.plan(MIX, seed, vocab=102400, horizon_s=60)


def sizes(p, k):
    return sorted((c["requests"][k]["prompt_len"], c["requests"][k]["max_new"])
                  for c in p["clients"])


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_001])
def test_same_seed_same_plan(seed):
    assert plan(seed) == plan(seed)
    p = plan(seed)["clients"][3]["requests"][2]["prompt_len"]
    assert gen.prompt_tokens(seed, 3, 2, p, 102400) == \
        gen.prompt_tokens(seed, 3, 2, p, 102400)


def test_seeds_deal_the_same_sizes():
    a, b = plan(1), plan(2)
    assert a != b
    for k in range(MIX["requests_per_client"]):
        prompts_a = sorted(c["requests"][k]["prompt_len"] for c in a["clients"])
        prompts_b = sorted(c["requests"][k]["prompt_len"] for c in b["clients"])
        assert prompts_a == prompts_b
        outs_a = sorted(c["requests"][k]["max_new"] for c in a["clients"])
        outs_b = sorted(c["requests"][k]["max_new"] for c in b["clients"])
        assert outs_a == outs_b


def test_length_distributions():
    p = plan(5)
    prompts = [r["prompt_len"] for c in p["clients"] for r in c["requests"]]
    outs = [r["max_new"] for c in p["clients"] for r in c["requests"]]
    assert min(prompts) >= 128 and max(prompts) <= 1792
    assert min(outs) >= 32 and max(outs) <= 256
    assert abs(statistics.median(prompts) - 768) <= 8
    assert abs(statistics.median(outs) - 128) <= 2
    # every prompt plus its output fits the engine's 2048 positions
    assert max(a + b for a, b in zip(prompts, outs)) <= 2048
    assert len({c["tenant"] for c in p["clients"]}) == 4


def test_prompt_tokens_in_vocab():
    t = gen.prompt_tokens(2 ** 40 + 3, 0, 0, 1000, 512)
    assert len(t) == 1000 and min(t) >= 1 and max(t) < 512


def test_open_loop_with_bursts():
    mix = gen.load("chat_burst")
    a = gen.plan(dict(mix, rate_rps=4.0), 11, vocab=1000, horizon_s=40)
    b = gen.plan(dict(mix, rate_rps=4.0), 12, vocab=1000, horizon_s=40)
    assert a["loop"] == "open"
    due = sorted(r["due"] for c in a["clients"] for r in c["requests"])
    due_b = sorted(r["due"] for c in b["clients"] for r in c["requests"])
    assert len(due) == len(due_b) and due != due_b
    assert sorted(r["prompt_len"] for c in a["clients"] for r in c["requests"]) \
        == sorted(r["prompt_len"] for c in b["clients"] for r in c["requests"])
    assert 0 <= due[0] and due[-1] < 40
    # three times as many arrivals in the bursts (first 2.5 s of every 10 s)
    # as the off-phase rate would give there
    on = sum(1 for t in due if t % 10 < 2.5)
    assert on == pytest.approx(0.75 * len(due), rel=0.15)
    # the mean rate is the mix's rate, bursts at factor x rate
    assert len(due) == pytest.approx(4.0 * 40, rel=0.1)
