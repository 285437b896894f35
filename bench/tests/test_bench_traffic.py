"""The traffic generator: sizes fixed by the mix, order and tokens by the
seed."""
import itertools
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic
from bench.tests.tiny import tiny_mix

BENCH = Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def first(mix, seed, n, vocab=1000):
    return list(itertools.islice(traffic.plan(mix, seed, vocab), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(BENCH / "traffic" / f"{name}.json")
    a, b = first(mix, 2 ** 31 + 17, 40), first(mix, 2 ** 31 + 17, 40)
    assert [(p.prompt, p.max_new, p.due_s) for p in a] == \
        [(p.prompt, p.max_new, p.due_s) for p in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_sizes_not_the_order(name):
    mix = traffic.load_mix(BENCH / "traffic" / f"{name}.json")
    n = mix.requests
    a, b = first(mix, 3, n), first(mix, 4, n)
    skip = mix.stagger   # the staggered head keeps a share of its output
    sizes = lambda ps: Counter((len(p.prompt), p.max_new) for p in ps[skip:])  # noqa
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    full_a = Counter(len(p.prompt) for p in a)
    assert full_a == Counter(len(p.prompt) for p in b)
    # the open loop's gaps are one multiset too: the last arrival is equal
    if mix.loop == "open":
        assert a[-1].due_s == pytest.approx(b[-1].due_s)
    assert sum(sizes(a).values()) == n - skip


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix(name):
    mix = traffic.load_mix(BENCH / "traffic" / f"{name}.json")
    prompts, outs, gaps = traffic.shapes(mix)
    assert set(prompts) <= set(mix.prompt_buckets)
    w = np.asarray(mix.prompt_weights) / sum(mix.prompt_weights)
    for bucket, share in zip(mix.prompt_buckets, w):
        assert np.mean(prompts == bucket) == pytest.approx(share, abs=0.03)
    assert outs.min() >= mix.out_min and outs.max() <= mix.out_max
    assert np.median(outs) == pytest.approx(mix.out_median, rel=0.08)
    assert max(prompts) + mix.out_max <= mix.capacity
    if mix.loop == "open":
        assert np.mean(gaps) == pytest.approx(1 / mix.rate_rps, rel=0.05)


def test_tokens_stay_in_the_vocabulary_and_stagger_shortens():
    mix = tiny_mix()
    reqs = first(mix, 9, 200, vocab=50)
    assert all(0 <= t < 50 for p in reqs for t in p.prompt)
    _, outs, _ = traffic.shapes(mix)
    assert all(1 <= p.max_new <= mix.out_max for p in reqs[:mix.stagger])


def test_a_mix_that_does_not_fit_is_refused(tmp_path):
    doc = json.loads((BENCH / "traffic" / f"{MIXES[0]}.json").read_text())
    doc["capacity"] = 64
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="does not fit"):
        traffic.load_mix(path)
