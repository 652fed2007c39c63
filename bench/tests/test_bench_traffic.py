"""The traffic generator: exact counts, exact class shares and seeded
topics; the ``topics`` deployment's filters drawn from a mix."""

import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib import traffic  # noqa: E402
from benchlib.spec import Bench  # noqa: E402

MIX = json.loads((pathlib.Path(__file__).resolve().parents[1]
                  / "traffic/uniform.json").read_text())


def topics_deployment():
    bench = Bench()
    cfg = bench.config("case-study-k64")
    dep = bench.deployment(cfg)
    return dep, dep.Deployment({**cfg, **cfg["rehearsal"]}, 3)


def test_window_holds_an_exact_count():
    for seed in (1, 2):
        t = traffic.schedule(MIX, 100.0, 10.0, np.random.default_rng(seed))
        assert ((t >= 0) & (t < 10)).sum() == 1000
        assert (t < 0).sum() == round(100 * MIX["lead_in_s"])
        assert (np.diff(t) >= 0).all()


def test_classes_in_exact_shares_and_filters_in_range():
    mod, dep = topics_deployment()
    r = dep.draw(MIX, 400, np.random.default_rng(3), stream=2)
    assert np.bincount(r.cls).tolist() == [100, 100, 100, 100]
    assert r.queries.shape == (400, 768)
    unfiltered = r.cls == r.class_names.index("unfiltered")
    assert (r.lo[unfiltered] == mod.ATTR_MIN).all()
    narrow = r.cls == r.class_names.index("sel~0.5%")
    assert (r.hi[narrow, 0] - r.lo[narrow, 0] == 999).all()
    assert (r.lo[narrow, 2] == 0).all() and (r.hi[narrow, 2] == 0).all()


def test_zipf_topics_in_a_seeded_rank_order():
    zipf = dict(MIX, topics={"dist": "zipf", "s": 1.0})
    w1 = traffic.topic_weights(zipf, 1024, np.random.default_rng(1))
    w2 = traffic.topic_weights(zipf, 1024, np.random.default_rng(2))
    assert np.isclose(w1.sum(), 1.0)
    assert np.isclose(np.sort(w1)[::-1][0] / np.sort(w1)[::-1][1], 2.0)
    assert np.argmax(w1) != np.argmax(w2) or not np.array_equal(w1, w2)


def test_hot_mix_concentrates_on_few_topics():
    hot = json.loads((pathlib.Path(__file__).resolve().parents[1]
                      / "traffic/hot-zipf1.json").read_text())
    t = traffic.topics(hot, 20000, 1024, np.random.default_rng(4))
    share = np.bincount(t, minlength=1024) / 20000
    top = 1.0 / np.sum(1.0 / np.arange(1, 1025))  # Zipf(1)'s first rank
    assert abs(share.max() - top) < 0.01
    assert (share > 0).sum() < 1024  # the tail is thin
    u = traffic.topics(MIX, 20000, 1024, np.random.default_rng(4))
    assert np.bincount(u, minlength=1024).max() / 20000 < 0.003
