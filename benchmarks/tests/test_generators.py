"""Generators are deterministic in the seed, differ across seeds in what
they send, and offer every seed the same schedule of sizes and arrivals."""
import itertools
import json
import os

import numpy as np
import pytest

from benchmarks.harness import registry

MIXES = ["chat-sessions", "batch-unshared"]
LOOPS = {"chat-sessions": {"kind": "open", "sessions_per_s": 2.0},
         "batch-unshared": {"kind": "closed", "clients": 8}}


def sessions(mix, seed, n=24):
    traffic = registry.load_json("traffic", mix)
    gen = registry.load_module("generators", traffic["generator"])
    plan = gen.plan(traffic["params"], LOOPS[mix], 1000, seed, horizon_s=30.0)
    return plan, list(itertools.islice(plan, n))


def flat(ss):
    return [(s.arrival_s, s.prefix_id, [(t.new_tokens.tolist(), t.max_tokens,
                                         t.think_s) for t in s.turns]) for s in ss]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_traffic(mix):
    assert flat(sessions(mix, 2**31 + 9)[1]) == flat(sessions(mix, 2**31 + 9)[1])


@pytest.mark.parametrize("mix", MIXES)
def test_other_seed_other_tokens(mix):
    a, b = sessions(mix, 1)[1], sessions(mix, 2)[1]
    assert flat(a) != flat(b)
    assert any(not np.array_equal(x.turns[0].new_tokens[-8:], y.turns[0].new_tokens[-8:])
               for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_set_of_sizes(mix):
    pa, _ = sessions(mix, 1)
    pb, _ = sessions(mix, 99)
    assert pa.shapes() == pb.shapes()
    if pa.arrivals is not None:
        assert np.array_equal(pa.arrivals, pb.arrivals)
    lens = lambda ss: [(s.arrival_s, [(len(t.new_tokens), t.max_tokens, t.think_s)
                                      for t in s.turns]) for s in ss]
    assert lens(sessions(mix, 1)[1]) == lens(sessions(mix, 99)[1])  # same order too
    assert pa.census(8, 16) == pb.census(8, 16)


def test_chat_mix_is_what_the_cell_says():
    plan, ss = sessions("chat-sessions", 3, n=40)
    shapes = plan.shapes()
    assert all(512 <= s["prefix_len"] <= 2048 for s in shapes)
    assert all(1 <= len(s["turn_tokens"]) <= 8 for s in shapes)
    assert all(16 <= t <= 1024 for s in shapes for t in s["turn_tokens"])
    assert all(16 <= t <= 512 for s in shapes for t in s["max_tokens"])
    assert len({s.prefix_id for s in ss}) > 1  # several system prompts in use
    assert ss[0].turns[0].think_s == 0.0
    assert all(a.arrival_s < b.arrival_s for a, b in zip(ss, ss[1:]))


def test_every_traffic_file_names_a_generator_that_exists():
    root = os.path.join(registry.ROOT, "traffic")
    for f in os.listdir(root):
        with open(os.path.join(root, f)) as fh:
            gen = json.load(fh)["generator"]
        assert os.path.isfile(os.path.join(registry.ROOT, "generators", gen + ".py"))
