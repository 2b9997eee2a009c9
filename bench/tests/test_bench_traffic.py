"""The traffic generator: deterministic per seed, the same work in another
order for every seed, lengths inside their clips, Zipf popularity, bursts,
length classes and shared prefixes as the mix states."""
import json

import numpy as np
import pytest

from toy import BENCH

from benchlib import traffic

NAMES = [f"expert{i:03d}" for i in range(8)]


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["code-zipf-open", "longdoc-closed"])
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 2**31 + 5, NAMES, 1000, 30.0)
    b = traffic.generate(mix(name), 2**31 + 5, NAMES, 1000, 30.0)
    ia = a.items or [i for c in a.clients for i in c]
    ib = b.items or [i for c in b.clients for i in c]
    assert len(ia) == len(ib)
    for x, y in zip(ia, ib):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.expert) == (y.max_new_tokens, y.expert)
    if a.offsets_s is not None:
        assert np.array_equal(a.offsets_s, b.offsets_s)


@pytest.mark.parametrize("name", ["code-zipf-open", "longdoc-closed"])
def test_lengths_keep_their_clips(name):
    p = mix(name)
    t = traffic.generate(p, 3, NAMES, 1000, 30.0)
    items = t.items or [i for c in t.clients for i in c]
    pl = np.array([len(i.prompt) for i in items])
    ol = np.array([i.max_new_tokens for i in items])
    assert pl.min() >= p["prompt"]["min"] and pl.max() <= p["prompt"]["max"]
    assert ol.min() >= p["output"]["min"] and ol.max() <= p["output"]["max"]
    assert abs(np.median(pl) - p["prompt"]["median"]) < 0.1 * p["prompt"][
        "median"]
    assert all(0 <= i.prompt.min() and i.prompt.max() < 1000 for i in items)


def test_zipf_shares_of_the_coe_mix():
    p = mix("code-zipf-open")
    shares = traffic.zipf_shares(8, p["experts"]["zipf_s"])
    assert shares[:4].sum() == pytest.approx(0.7895, abs=5e-4)
    t = traffic.generate(p, 5, NAMES, 1000, 1000.0)
    top4 = sum(i.expert in t.popularity[:4] for i in t.items)
    assert abs(top4 / len(t.items) - shares[:4].sum()) < 0.03
    # the ranks are mapped onto experts by the seed
    other = traffic.generate(p, 6, NAMES, 1000, 30.0)
    assert other.popularity != t.popularity


def test_open_loop_rate():
    p = dict(mix("code-zipf-open"), rate_per_s=5.0)
    t = traffic.generate(p, 9, NAMES, 1000, 30.0)
    assert np.all(np.diff(t.offsets_s) > 0)
    assert len(t.items) == 150 and 0 < t.offsets_s[0]
    assert t.offsets_s[-1] < 30.0
    assert len(t.items) / t.offsets_s[-1] == pytest.approx(5.0, rel=0.1)


@pytest.mark.parametrize("name", ["code-zipf-open", "longdoc-closed"])
def test_seeds_replay_one_schedule(name):
    """Every seed gets the same multiset of lengths, ranks and gaps, in
    an order, pairing and arrival schedule of its own, with its own token
    ids."""
    p = mix(name)
    a = traffic.generate(p, 1, NAMES, 1000, 30.0)
    b = traffic.generate(p, 2**33 + 7, NAMES, 1000, 30.0)
    ia = a.items or [i for c in a.clients for i in c]
    ib = b.items or [i for c in b.clients for i in c]
    if a.offsets_s is not None:
        assert sorted(np.diff(a.offsets_s, prepend=0)) == pytest.approx(
            sorted(np.diff(b.offsets_s, prepend=0)))
        assert not np.allclose(a.offsets_s, b.offsets_s)
    assert sorted(len(i.prompt) for i in ia) == \
        sorted(len(i.prompt) for i in ib)
    assert sorted(i.max_new_tokens for i in ia) == \
        sorted(i.max_new_tokens for i in ib)
    assert sorted(a.popularity.index(i.expert) for i in ia) == \
        sorted(b.popularity.index(i.expert) for i in ib)
    assert [len(i.prompt) for i in ia] != [len(i.prompt) for i in ib]
    assert not np.array_equal(ia[0].prompt, ib[0].prompt)


def test_closed_loop_clients():
    p = mix("longdoc-closed")
    t = traffic.generate(p, 9, ["expert000"], 1000, 30.0)
    assert len(t.clients) == p["clients"]
    assert all(len(c) == p["n_requests"] for c in t.clients)
    assert t.offsets_s is None and not t.items


def test_closed_loop_depths_hold_the_same_work():
    """Each client's j-th requests take one of each stratum of lengths,
    so the first few depths, all a window consumes, hold nearly the same
    tokens for every seed, in another order."""
    p = mix("longdoc-closed")
    runs = [traffic.generate(p, s, ["expert000"], 1000, 30.0)
            for s in (3, 2**31 + 11, 2**33 + 1)]
    for key in (lambda i: len(i.prompt), lambda i: i.max_new_tokens):
        per_depth = np.array([[sum(key(c[j]) for c in t.clients)
                               for j in range(4)] for t in runs])
        assert np.ptp(per_depth, axis=0).max() < 0.02 * per_depth.min()
        firsts = [[key(c[0]) for c in t.clients] for t in runs]
        assert firsts[0] != firsts[1]


def test_bursty_arrivals():
    """Gamma gaps of coefficient of variation 3.5 keep the rate and the
    window, and bunch arrivals far more than Poisson gaps do."""
    p = dict(mix("code-zipf-open"), rate_per_s=4.0)
    calm = traffic.generate(p, 4, NAMES, 1000, 50.0)
    burst = traffic.generate(dict(p, arrivals={"process": "gamma",
                                               "cv": 3.5}), 4, NAMES, 1000,
                             50.0)
    for t in (calm, burst):
        assert len(t.items) == 200
        assert t.offsets_s[-1] == pytest.approx(50.0 * 200 / 201)
    cv = lambda t: np.std(np.diff(t.offsets_s)) / np.mean(np.diff(
        t.offsets_s))
    assert 0.8 < cv(calm) < 1.2
    assert cv(burst) > 2.5
    with pytest.raises(ValueError):
        traffic.generate(dict(p, arrivals={"process": "weibull"}), 4, NAMES,
                         1000, 50.0)


def test_length_classes_and_shared_prefixes():
    """Two length classes in one queue, in their shares, and a share of
    requests opening with one of a few shared prefixes."""
    p = {"loop": "open", "rate_per_s": 10.0, "experts": {},
         "classes": [
             {"share": 0.1, "prompt": {"median": 3000, "sigma": 0.1,
                                       "min": 2800, "max": 3200},
              "output": {"median": 64, "sigma": 0.2, "min": 32, "max": 96}},
             {"share": 0.9, "prompt": {"median": 100, "sigma": 0.3,
                                       "min": 50, "max": 200},
              "output": {"median": 32, "sigma": 0.2, "min": 16, "max": 48}}],
         "prefix": {"share": 0.5, "count": 3,
                    "length": {"median": 500, "sigma": 0.2, "min": 400,
                               "max": 600}}}
    t = traffic.generate(p, 11, NAMES, 1000, 20.0)
    assert len(t.items) == 200
    heads = {}
    for i in t.items:
        heads.setdefault(tuple(i.prompt[:400]), []).append(i)
    shared = [k for k, v in heads.items() if len(v) > 1]
    assert len(shared) == 3
    assert sum(len(heads[k]) for k in shared) == 100
    # the long class, with or without a prefix, and nothing else is long
    assert sum(len(i.prompt) >= 2800 for i in t.items) == 20
    assert traffic.apportion(200, [0.1, 0.9]).tolist() == [20, 180]
