"""The traffic generator: a seed fixes the inputs, every seed offers the
same work in its own order, and every prompt leaves room for its output."""
import collections
import json
from pathlib import Path

import pytest

from bench import traffic

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                  / "interactive-teams.json").read_text())


def _work(jobs):
    return sorted((j.app, j.team, tuple((s.prompt_len, s.out_len)
                                        for s in j.stages)) for j in jobs)


def test_same_seed_same_inputs():
    a = traffic.generate(MIX, 2**33 + 17)
    b = traffic.generate(MIX, 2**33 + 17)
    assert [(j.team, j.app) for j in a] == [(j.team, j.app) for j in b]
    s = a[0].stages[-1]
    assert traffic.prompt_tokens(a[0], 0, s, 151936, 5) == \
        traffic.prompt_tokens(b[0], 0, s, 151936, 5)


def test_seeds_offer_one_backlog_in_their_own_order():
    a = traffic.generate(MIX, 1)
    b = traffic.generate(MIX, 2**40 + 3)
    assert len(a) == len(b) == MIX["arrival"]["jobs"]
    assert _work(a) == _work(b)
    assert [j.app for j in a] != [j.app for j in b]
    teams = collections.Counter(j.team for j in a)
    assert sorted(teams) == list(range(MIX["teams"]))
    assert max(teams.values()) - min(teams.values()) <= 1
    s = a[0].stages[0]
    assert traffic.prompt_tokens(a[0], 0, s, 151936, 1) != \
        traffic.prompt_tokens(a[0], 0, s, 151936, 2**40 + 3)


def test_prompts_share_blocks_and_fit_the_window():
    jobs = traffic.generate(dict(MIX, arrival={"kind": "backlog",
                                               "jobs": 200}), 3)
    cap, ctx = MIX["output_cap"], MIX["context_tokens"]
    for j in jobs:
        for s in j.stages:
            assert s.prompt_len + cap <= ctx - 1
            assert s.blocks[0] == ("sys", MIX["system_tokens"])
            assert 4 <= s.out_len <= cap
            for d in s.deps:                   # a child carries its parent
                parent = j.stages[d].blocks
                if s.blocks[:len(parent)] != parent:
                    assert s.blocks[1][0].startswith("reply:")
    a, b = jobs[0], next(j for j in jobs[1:] if j.team == jobs[0].team)
    ta = traffic.prompt_tokens(a, 0, a.stages[0], 151936, 3)
    tb = traffic.prompt_tokens(b, jobs.index(b), b.stages[0], 151936, 3)
    assert ta[:MIX["system_tokens"]] == tb[:MIX["system_tokens"]]
    assert ta[MIX["system_tokens"] + MIX["role_tokens"]:] != \
        tb[MIX["system_tokens"] + MIX["role_tokens"]:]


def test_unknown_arrival_kind_is_refused():
    with pytest.raises(ValueError, match="arrival kind"):
        traffic.generate(dict(MIX, arrival={"kind": "poisson"}), 1)
