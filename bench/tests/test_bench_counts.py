"""The traffic generators: a function of the seed, the same load for
every seed."""
import numpy as np

from _tiny import ROOT
from bench import arrivals, common
from bench.corpus import Corpus


def test_corpus_is_a_function_of_the_seed():
    t = common.read_json(common.BENCH, "traffic", "pretrain_4k_b1.json")
    t.update(seq_len=32, batch=2)
    a = Corpus(t, 1000, 2**31 + 3, 4)
    b = Corpus(t, 1000, 2**31 + 3, 2)
    b.extend(4)
    c = Corpus(t, 1000, 2**31 + 4, 4)
    for s in range(4):
        np.testing.assert_array_equal(a.batch_at(s)["tokens"],
                                      b.batch_at(s)["tokens"])
        assert (a.batch_at(s)["tokens"] < 1000).all()
        np.testing.assert_array_equal(a.batch_at(s)["tokens"][:, 1:],
                                      a.batch_at(s)["labels"][:, :-1])
    assert not np.array_equal(a.batch_at(0)["tokens"], c.batch_at(0)["tokens"])


def test_schedule_is_the_same_for_every_seed():
    bm = common.read_json(ROOT, "BENCHMARK.json")
    t = common.read_json(common.BENCH, "traffic",
                         f"{bm['workloads'][0]['traffic']}.json")
    s = arrivals.schedule(t, 30.0)
    assert s == arrivals.schedule(t, 30.0)
    assert abs(len(s) - 30 * t["rate_per_s"]) < 4 * (30 * t["rate_per_s"]) ** .5
    lo, hi = t["output_len"]["min"], t["output_len"]["max"]
    assert all(lo <= n <= hi for _, n in s)
    p1 = arrivals.prompts(t, 151936, 2**31 + 1, 3)
    p2 = arrivals.prompts(t, 151936, 2**31 + 2, 3)
    assert all(len(p) == t["prompt_len"] for p in p1)
    assert not np.array_equal(p1[0], p2[0])
