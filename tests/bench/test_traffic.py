"""The traffic generator: deterministic in the seed, the same work for
every seed, and the length distributions it reports."""
import json

import numpy as np
import pytest

from bench import traffic
from bench.spec import BENCH

SEED = 2**31 + 12345


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,cell", [
    ("decode-batch", {"n_slots": 32}),
    ("long-prompt-poisson", {"n_slots": 16, "rate_per_s": 5.0}),
])
def test_deterministic_in_seed(name, cell):
    a = traffic.generate(mix(name), cell, SEED, 10.0, 50272)
    b = traffic.generate(mix(name), cell, SEED, 10.0, 50272)
    c = traffic.generate(mix(name), cell, SEED + 1, 10.0, 50272)
    key = lambda rs: [(r.prompt.tolist(), r.max_new, r.sampling, r.due)
                      for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name,cell", [
    ("decode-batch", {"n_slots": 32}),
    ("long-prompt-poisson", {"n_slots": 16, "rate_per_s": 5.0}),
])
def test_same_work_every_seed(name, cell):
    """Seeds reorder one multiset of lengths, gaps and sampled flags."""
    runs = [traffic.generate(mix(name), cell, s, 10.0, 50272)
            for s in (1, 2**31 + 7, 2**33 + 5)]
    for rs in runs[1:]:
        assert sorted(len(r.prompt) for r in rs) == sorted(
            len(r.prompt) for r in runs[0])
        assert (sum(r.sampling is not None for r in rs)
                == sum(r.sampling is not None for r in runs[0]))
    if mix(name)["arrival"] == "poisson":
        for rs in runs:
            due = np.array([r.due for r in rs])
            assert (np.diff(due[due >= 3.0]) > 0).all()
            assert len(due[due >= 3.0]) == 50  # rate x seconds
            assert due.min() == 0.0 and due.max() < 13.0


def test_lengths_follow_the_mix():
    m = mix("decode-batch")
    d = traffic.describe(traffic.generate(m, {"n_slots": 32}, 5, 10.0,
                                          50272)[32:])
    assert d["prompt"]["min"] >= m["prompt"]["min"]
    assert d["prompt"]["max"] <= m["prompt"]["max"]
    assert abs(d["prompt"]["p50"] - m["prompt"]["median"]) <= 4
    assert abs(d["sampled"] / d["n"] - m["sampled_share"]) < 0.03


def test_first_wave_is_staggered():
    rs = traffic.generate(mix("decode-batch"), {"n_slots": 32}, 5, 10.0,
                          50272)
    pool = traffic.lognormal_lengths(mix("decode-batch")["output"], 1024)
    assert rs[0].max_new <= -(-pool.max() // 32)  # cut to 1/32 of its draw
    assert rs[40].max_new >= pool.min()  # past the first wave: uncut
    assert len({r.max_new for r in rs[:32]}) > 16


def test_quantile_lengths_are_the_distribution():
    x = traffic.lognormal_lengths({"median": 100, "sigma": 0.5, "min": 1,
                                   "max": 10**6}, 1001)
    assert np.median(x) == 100
    assert np.all(np.diff(x) >= 0)


def test_schedule_seed_replays_one_trace():
    """With ``schedule_seed`` every run seed replays the same arrivals,
    lengths and sampled flags; the run seed draws only the tokens."""
    m = mix("long-prompt-poisson")
    cell = {"n_slots": 8, "rate_per_s": 1.3}
    shape = lambda rs: [(len(r.prompt), r.max_new, r.sampling is not None,
                         r.due) for r in rs]
    a, b = (traffic.generate(m, cell, s, 51.0, 50272)
            for s in (SEED, SEED + 1))
    assert shape(a) == shape(b)
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    free = {k: v for k, v in m.items() if k != "schedule_seed"}
    c, d = (traffic.generate(free, cell, s, 51.0, 50272)
            for s in (SEED, SEED + 1))
    assert shape(c) != shape(d)
