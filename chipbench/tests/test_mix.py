"""Traffic repeats exactly for a seed, and every seed gets the same work."""
import numpy as np
import pytest

from chipbench import mix as MIX

SEEDS = [7, 2**31 + 11]


def lens(reqs):
    return sorted(len(r.prompt) for r in reqs), sorted(r.max_new for r in reqs)


@pytest.mark.parametrize("name", ["chat", "decode"])
def test_same_seed_same_requests(name):
    mix = MIX.load_mix(name)
    a = MIX.requests(mix, SEEDS[1], 40, 32, 64000)
    b = MIX.requests(mix, SEEDS[1], 40, 32, 64000)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.rid, x.max_new, x.due) == (y.rid, y.max_new, y.due)
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["chat", "decode"])
def test_seeds_share_the_work(name):
    mix = MIX.load_mix(name)
    a, b = (MIX.requests(mix, s, 40, 32, 64000) for s in SEEDS)
    assert lens(a) == lens(b)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    if mix["kind"] == "open_loop":
        # the same gaps in another order, every request due in the window
        ga, gb = np.diff([r.due for r in a]), np.diff([r.due for r in b])
        both = np.intersect1d(np.round(ga, 9), np.round(gb, 9))
        assert len(both) >= len(ga) - 1
        assert max(r.due for r in a + b) < 40
        assert [r.max_new for r in a] != [r.max_new for r in b]


def test_chat_lengths_follow_the_mix():
    mix = MIX.load_mix("chat")
    reqs = MIX.requests(mix, 3, 40, 32, 64000)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert len(reqs) == round(mix["rate_per_s"] * 40)
    assert p.min() >= 64 and p.max() <= 3584
    assert 900 <= np.median(p) <= 1150 and 110 <= np.median(o) <= 145
    assert all((r.prompt >= 1).all() and (r.prompt < 64000).all() for r in reqs)


def test_backlog_blocks_cover_the_distribution():
    mix = MIX.load_mix("decode")
    reqs = MIX.requests(mix, 5, 40, 32, 64000)
    first = [r.max_new for r in reqs[:MIX.BLOCK]]
    assert min(first) < 560 and max(first) > 980
    assert all(r.due == 0.0 for r in reqs)


def test_buckets():
    assert MIX.buckets(MIX.load_mix("chat"), 4096) == [
        64, 128, 256, 512, 1024, 2048, 4096]
    assert MIX.buckets(MIX.load_mix("decode"), 2048) == [128, 256, 512]
