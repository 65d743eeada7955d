import numpy as np

from bench.generators import requests as G
from conftest import TRAFFIC

OPEN = {"generator": "requests", "loop": "open", "rate_per_s": 2.0,
        "prompt_len": {"lognormal": {"median": 768, "sigma": 0.7,
                                     "min": 128, "max": 1920}},
        "output_len": {"lognormal": {"median": 24, "sigma": 0.8,
                                     "min": 8, "max": 128}},
        "max_total": 2048}


def _key(plan):
    return [(p.due_s, p.prompt.tolist(), p.max_new_tokens) for p in plan]


def test_deterministic_per_seed():
    big = 2 ** 31 + 12345
    a = G.generate(OPEN, vocab_size=100352, seed=big, seconds=30)
    b = G.generate(OPEN, vocab_size=100352, seed=big, seconds=30)
    c = G.generate(OPEN, vocab_size=100352, seed=big + 1, seconds=30)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_same_work_every_seed_and_within_clips():
    runs = [G.generate(OPEN, vocab_size=100352, seed=s, seconds=30)
            for s in (1, 2, 3)]
    for plan in runs:
        assert len(plan) == 60
        plens = np.array([len(p.prompt) for p in plan])
        olens = np.array([p.max_new_tokens for p in plan])
        assert plens.min() >= 128 and plens.max() <= 1920
        assert olens.min() >= 8 and olens.max() <= 128
        assert (plens + olens <= 2048).all()
        dues = np.array([p.due_s for p in plan])
        assert dues[0] == 0 and (np.diff(dues) > 0).all()
        assert dues[-1] < 30
        for p in plan:
            assert p.prompt.dtype == np.int32
            assert p.prompt.min() >= 0 and p.prompt.max() < 100352
    # the same multiset of sizes and gaps, in another order
    for plan in runs[1:]:
        for f in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
            assert sorted(map(f, plan)) == sorted(map(f, runs[0]))
        gaps = lambda pl: sorted(np.round(np.diff([p.due_s for p in pl]
                                                   + [30.0]), 9))
        assert np.allclose(gaps(plan), gaps(runs[0]))


def test_closed_loop():
    plan = G.generate(TRAFFIC["tiny-closed"], vocab_size=512, seed=3,
                      seconds=5)
    assert [p.due_s for p in plan] == [None, None]
    assert [len(p.prompt) for p in plan] == [150, 150]
    assert [p.max_new_tokens for p in plan] == [60, 60]


def test_blocks_hold_the_same_pairs_every_seed():
    mix = dict(OPEN, block=4)
    plans = [G.generate(mix, vocab_size=100352, seed=s, seconds=30)
             for s in (1, 2 ** 31 + 5)]
    pairs = lambda pl: sorted((len(p.prompt), p.max_new_tokens) for p in pl)
    for plan in plans:
        for i in range(0, 60, 4):
            assert pairs(plan[i:i + 4]) == pairs(plans[0][:4])
    assert len(set(pairs(plans[0][:4]))) == 4
    assert [len(p.prompt) for p in plans[0]] != \
        [len(p.prompt) for p in plans[1]]


def test_unshuffled_sizes_and_gaps_are_the_same_every_seed():
    mix = dict(OPEN, block=4, shuffle=False)
    a, b = (G.generate(mix, vocab_size=100352, seed=s, seconds=30)
            for s in (3, 2 ** 31 + 9))
    sizes = lambda pl: [(p.due_s, len(p.prompt), p.max_new_tokens)
                        for p in pl]
    assert sizes(a) == sizes(b)
    assert a[0].prompt.tolist() != b[0].prompt.tolist()
