"""The traffic generator: seeded, bounded, and the same work for every seed."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import Schedule, rate_for_block, seed_key

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
BIG = 2**31 + 12345


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def schedule(name, seed, n):
    s = Schedule(mix(name), seed, 65536)
    return s, [s.request(i) for i in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    s1, a = schedule(name, BIG, 300)
    s2, b = schedule(name, BIG, 300)
    assert a == b
    assert all((s1.tokens(i) == s2.tokens(i)).all() for i in range(5))


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_tokens_same_trace(name):
    """The committed mixes replay one trace: another seed draws other
    prompt tokens for the same requests at the same times."""
    assert "schedule_seed" in mix(name)
    s1, a = schedule(name, BIG, 300)
    s2, b = schedule(name, BIG + 1, 300)
    assert a == b
    assert not (s1.tokens(0)[:8] == s2.tokens(0)[:8]).all()


@pytest.mark.parametrize("name", MIXES)
def test_without_schedule_seed_the_seed_reorders(name):
    m = {k: v for k, v in mix(name).items() if k != "schedule_seed"}
    a = [Schedule(m, BIG, 65536).request(i) for i in range(300)]
    b = [Schedule(m, BIG + 1, 65536).request(i) for i in range(300)]
    assert a != b
    assert sorted(r.max_new for r in a[:m["block"]]) == sorted(
        r.max_new for r in b[:m["block"]])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_buckets_and_limits(name):
    m = mix(name)
    s, reqs = schedule(name, BIG, 500)
    assert {r.prompt_len for r in reqs} <= set(m["prompt_buckets"])
    assert all(m["output"]["min"] <= r.max_new <= m["output"]["max"]
               for r in reqs)
    assert all(r.prompt_len + r.max_new <= m["max_seq"] + 1 for r in reqs)
    assert all(len(s.tokens(i)) == reqs[i].prompt_len for i in range(20))
    assert all(0 <= t < 65536 for i in range(20) for t in s.tokens(i))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    """Each block holds the same sizes and gaps; the seed only reorders."""
    m = {k: v for k, v in mix(name).items() if k != "schedule_seed"}
    block = m["block"]
    a = [Schedule(m, 1, 65536).request(i) for i in range(2 * block)]
    b = [Schedule(m, BIG, 65536).request(i) for i in range(2 * block)]
    for lo in (0, block):
        pa = Counter((r.prompt_len, ) for r in a[lo:lo + block])
        pb = Counter((r.prompt_len, ) for r in b[lo:lo + block])
        assert pa == pb
        assert sorted(r.max_new for r in a[lo:lo + block]) == sorted(
            r.max_new for r in b[lo:lo + block])
    assert a[-1].due_s == pytest.approx(b[-1].due_s)


@pytest.mark.parametrize("name", MIXES)
def test_prefill_shapes_bounded_by_admit_per_tick(name):
    m = mix(name)
    s = Schedule(m, BIG, 65536)
    shapes = s.shapes()
    assert len(shapes) == m["admit_per_tick"] * len(m["prompt_buckets"])
    assert max(g for g, _ in shapes) == m["admit_per_tick"]


def test_poisson_rate_and_order():
    m = mix("chat_poisson")
    assert rate_for_block(m) == pytest.approx(m["rate_per_s"], rel=0.02)
    _, reqs = schedule("chat_poisson", BIG, 250)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] > 0
    assert len(reqs) / due[-1] == pytest.approx(m["rate_per_s"], rel=0.05)


def test_prompt_shares_follow_weights():
    m = mix("chat_poisson")
    _, reqs = schedule("chat_poisson", BIG, m["block"])
    got = Counter(r.prompt_len for r in reqs)
    want = np.asarray(m["prompt_weights"]) * m["block"]
    assert [got[p] for p in m["prompt_buckets"]] == pytest.approx(
        want, abs=1)


def test_seed_key_takes_large_seeds():
    assert seed_key(BIG) != seed_key(BIG + 1)
    assert 0 <= seed_key(2**40) < 2**32
