"""Traffic mixes: deterministic per seed, the same work under every seed,
and the shares their fits declare."""
import json
import math
import sys
from pathlib import Path
from statistics import NormalDist

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402,F401
from benchlib import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (bench_tiny.BENCH / "traffic").glob("*.json"))
BIG_SEED = 2**33 + 12345


def mix(name):
    return json.loads((bench_tiny.BENCH / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_per_seed(name):
    a = traffic.generate(mix(name), rate=4.0, n=300, vocab=1000,
                         seed=BIG_SEED)
    b = traffic.generate(mix(name), rate=4.0, n=300, vocab=1000,
                         seed=BIG_SEED)
    c = traffic.generate(mix(name), rate=4.0, n=300, vocab=1000, seed=7)
    assert [(r.arrival, r.prompt_len, r.max_new) for r in a] == \
        [(r.arrival, r.prompt_len, r.max_new) for r in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    assert [r.prompt_len for r in a] != [r.prompt_len for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_seed_changes_order_not_work(name):
    m = mix(name)
    block = m["block"]
    a = traffic.generate(m, rate=4.0, n=4 * block, vocab=1000, seed=1)
    b = traffic.generate(m, rate=4.0, n=4 * block, vocab=1000, seed=2)
    for i in range(4):
        blk = slice(i * block, (i + 1) * block)
        assert sorted(r.prompt_len for r in a[blk]) == \
            sorted(r.prompt_len for r in b[blk])
        assert sorted(r.max_new for r in a[blk]) == \
            sorted(r.max_new for r in b[blk])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_match_declared_fit(name):
    m = mix(name)
    reqs = traffic.generate(m, rate=4.0, n=10 * m["block"], vocab=1000,
                            seed=3)
    ins = np.array([r.prompt_len for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    assert set(ins) <= set(m["lengths"])
    assert ins.max() <= m["input"]["max"]
    # long share: the lognormal's mass above the 2048 threshold, capped at
    # the clip (prompts are only rounded up within the same side)
    i = m["input"]
    p_long = 0.0
    if i["max"] >= 2048:
        z = math.log(2048 / i["median"]) / i["sigma"]
        p_long = 1.0 - NormalDist().cdf(z)
    assert abs((ins >= 2048).mean() - p_long) < 0.03
    assert abs(np.median(outs) - m["output"]["median"]) <= 2
    assert outs.max() <= m["output"]["max"]


@pytest.mark.parametrize("name", MIXES)
def test_offered_rate(name):
    reqs = traffic.generate(mix(name), rate=4.0, n=2000, vocab=10, seed=5)
    assert abs(traffic.summary(reqs, 2048)["rate"] - 4.0) < 0.4


def test_azure_mixed_long_share():
    reqs = traffic.generate(mix("azure_mixed"), rate=4.0, n=500, vocab=10,
                            seed=11)
    s = traffic.summary(reqs, 2048)
    assert 0.15 <= s["long_share"] <= 0.22


@pytest.mark.parametrize("name", [n for n in MIXES
                                  if "output_strata" in mix(n)])
def test_each_class_carries_the_same_outputs_in_every_block(name):
    """With `output_strata` the outputs of each class of prompts, not only
    of the whole block, are the same under every seed."""
    m = mix(name)
    block, cut = m["block"], m["output_strata"][0]
    reqs = {s: traffic.generate(m, rate=4.0, n=4 * block, vocab=10, seed=s)
            for s in (BIG_SEED, 5)}
    for i in range(4):
        blk = slice(i * block, (i + 1) * block)
        for long in (False, True):
            got = [sorted(r.max_new for r in reqs[s][blk]
                          if (r.prompt_len >= cut) == long) for s in reqs]
            assert got[0] == got[1] and got[0]
    longs = [r.max_new for r in reqs[5][:block] if r.prompt_len >= cut]
    assert sorted(longs) == sorted(traffic.lognormal_block(m["output"],
                                                          len(longs)))
