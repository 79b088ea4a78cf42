"""Generators: the same seed gives the same traffic; every seed gives
the same sizes in another order; seeds past 32 bits work."""
import numpy as np

from bench.generators import backlog, poisson, strata


def _sizes(reqs):
    return sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs)


def test_backlog_deterministic(tiny_backlog):
    t = dict(tiny_backlog, requests=64)
    a = backlog.generate(t, 2**40 + 7, 300)
    b = backlog.generate(t, 2**40 + 7, 300)
    assert [r["prompt"].tolist() for r in a] == [r["prompt"].tolist() for r in b]
    c = backlog.generate(t, 5, 300)
    assert _sizes(a) == _sizes(c)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    assert all(r["arrival_s"] == 0.0 for r in a)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= t["max_total"] for r in a)


def test_poisson_same_load_every_seed(tiny_backlog):
    t = dict(tiny_backlog, requests=64, rate_per_s=4.0)
    a = poisson.generate(t, 11, 300)
    b = poisson.generate(t, 3**30, 300)
    assert _sizes(a) == _sizes(b)
    ga = np.diff([r["arrival_s"] for r in a])
    gb = np.diff([r["arrival_s"] for r in b])
    assert np.all(ga >= 0) and np.all(gb >= 0)
    # each block of 8 spans the same time whatever the seed
    ta = np.array([r["arrival_s"] for r in a])
    tb = np.array([r["arrival_s"] for r in b])
    assert abs((ta[15] - ta[7]) - (tb[15] - tb[7])) < 1.0
    assert abs(ta[-1] - tb[-1]) < 1.0
    assert [r["prompt"].tolist() for r in poisson.generate(t, 11, 300)] == \
        [r["prompt"].tolist() for r in a]


def test_quantiles():
    q = strata.lognormal_quantiles(5, 100, 1.0, 10, 1000)
    assert q[2] == 100 and list(q) == sorted(q)
    g = strata.exponential_quantiles(1000, 2.0)
    assert abs(g.mean() - 0.5) < 0.01


def test_train_batches_differ_per_step(tiny_m6, tiny_pretrain):
    from bench import seeds
    from bench.generators import train_batches

    fn = train_batches.batch_fn(tiny_pretrain, tiny_m6)
    key = seeds.jax_key(2**33 + 1, "traffic.batches")
    b0, b1, b0again = fn(key, 0), fn(key, 1), fn(key, 0)
    assert (np.asarray(b0["tokens"]) == np.asarray(b0again["tokens"])).all()
    assert (np.asarray(b0["tokens"]) != np.asarray(b1["tokens"])).any()
    assert b0["patch_embeds"].shape == (4, 4, 64)
    assert (np.asarray(b0["labels"])[:, :-1] == np.asarray(b0["tokens"])[:, 1:]).all()


def test_order_seed_fixes_the_schedule(tiny_backlog):
    t = dict(tiny_backlog, requests=32, rate_per_s=2.0, order_seed=5)
    a = poisson.generate(t, 1, 300)
    b = poisson.generate(t, 2**36, 300)
    assert [(len(r["prompt"]), r["max_new_tokens"], r["arrival_s"]) for r in a] == \
        [(len(r["prompt"]), r["max_new_tokens"], r["arrival_s"]) for r in b]
    assert [r["prompt"].tolist() for r in a] != [r["prompt"].tolist() for r in b]
