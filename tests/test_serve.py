"""Serving engine: continuous batching, determinism, snapshot/restore,
heterogeneous profiling, the cache-length cap, one device read per
decode, batched serving against serving alone."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.models.model_zoo import build_model
from repro.serve import Request, ServeEngine, SyntheticRequests


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen3-1.7b"))
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def test_engine_completes_all_requests(setup):
    cfg, m, params = setup
    eng = ServeEngine(cfg, batch=3, max_seq=96, prefill_len=16,
                      instrument=False)
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=12, mean_new=8, seed=0)
    reqs = [gen.request(i) for i in range(7)]
    stats = eng.run(params, reqs)
    assert stats["requests"] == 7
    assert stats["tokens"] > 7
    assert stats["tokens_per_s"] > 0
    for r in eng.done:
        assert len(r.output) >= 2


def test_greedy_decoding_deterministic(setup):
    cfg, m, params = setup
    outs = []
    for _ in range(2):
        eng = ServeEngine(cfg, batch=2, max_seq=64, prefill_len=8,
                          instrument=False)
        gen = SyntheticRequests(cfg.vocab_size, prompt_len=8, mean_new=6,
                                seed=1)
        eng.run(params, [gen.request(i) for i in range(3)])
        outs.append([tuple(r.output) for r in
                     sorted(eng.done, key=lambda r: r.req_id)])
    assert outs[0] == outs[1]


def test_profile_mixes_kinds(setup):
    cfg, m, params = setup
    eng = ServeEngine(cfg, batch=2, max_seq=64, prefill_len=8,
                      interval_steps=2.0)
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=8, mean_new=6, seed=0)
    eng.run(params, [gen.request(i) for i in range(4)])
    assert "prefill" in eng.kinds_log and "decode" in eng.kinds_log
    prof = eng.profile()
    assert prof.n_intervals >= 1
    # prefill and decode blocks both appear in the shared id space
    names = prof.table.names
    assert any(n.startswith("prefill/") for n in names)
    assert any(n.startswith("decode/") for n in names)


def test_length_cap_retires_at_last_cache_position(setup):
    """A request whose budget runs past the cache retires on the decode that
    writes the cache's last position, bringing its slot's cache length to
    ``max_seq``; one with a small budget still retires on its budget."""
    cfg, m, params = setup
    max_seq, prefill_len = 32, 8
    eng = ServeEngine(cfg, batch=2, max_seq=max_seq, prefill_len=prefill_len,
                      instrument=False)
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=8, seed=3)
    long_req, short_req = gen.request(0), gen.request(1)
    long_req.max_new_tokens = 100           # past max_seq - prefill_len
    short_req.max_new_tokens = 5
    eng.submit(long_req)
    eng.submit(short_req)
    lens = []                               # slot 0's length while it serves
    while eng.step(params):
        if eng.slot_req[0] is long_req or long_req.finished_at:
            lens.append(int(np.asarray(eng.cache["length"])[0]))
        if long_req.finished_at:
            break
    assert lens[-1] == max_seq
    assert max(lens[:-1]) < max_seq
    assert len(long_req.output) == max_seq - prefill_len + 1
    eng.run(params, [])
    assert len(short_req.output) == 1 + short_req.max_new_tokens
    assert sorted(r.req_id for r in eng.done) == [0, 1]


def test_last_cache_position_serves_the_same_tokens(setup):
    """A request that fills its slot's cache to the last position is
    served the tokens an engine with room to spare serves it."""
    cfg, m, params = setup
    max_seq, prefill_len = 24, 8
    outs = []
    for room in (max_seq, 2 * max_seq):
        eng = ServeEngine(cfg, batch=2, max_seq=room,
                          prefill_len=prefill_len, instrument=False)
        req = SyntheticRequests(cfg.vocab_size, prompt_len=8,
                                seed=5).request(0)
        req.max_new_tokens = max_seq - prefill_len
        eng.run(params, [req])
        outs.append(req.output)
    assert len(outs[0]) == max_seq - prefill_len + 1
    assert outs[0] == outs[1]


def test_one_device_read_per_decode_iteration(setup):
    """With tracing on, each decode iteration makes exactly one blocking
    read, of every slot's token and cache length, and serves the same
    tokens as with tracing off."""
    cfg, m, params = setup
    eng = ServeEngine(cfg, batch=3, max_seq=48, prefill_len=8,
                      instrument=False)
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=8, mean_new=8, seed=4)

    def served():
        eng.reset()
        eng.run(params, [gen.request(i) for i in range(5)])
        return {r.req_id: r.output for r in eng.done}

    untraced = served()
    t = obs.configure(trace=True)
    try:
        traced = served()
        evs = [e for e in t.events() if e["ph"] == "X"]
    finally:
        obs.configure(trace=False)
    assert traced == untraced and len(traced) == 5
    steps = [e for e in evs if e["name"] == "serve.step"
             and e["args"]["kind"] == "decode"]
    assert steps
    for s in steps:
        reads = [e["name"] for e in evs if e["name"].startswith("serve.read_")
                 and s["ts"] <= e["ts"] <= s["ts"] + s["dur"]]
        assert reads == ["serve.read_tokens"]


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "moonlight-16b-a3b"])
def test_batched_serving_matches_serving_alone(arch):
    """Requests served together, under continuous batching with slots
    reused at other rows' lengths, get exactly the tokens each gets served
    alone at batch 1: a row's in-place cache writes leave the other slots
    alone."""
    cfg = reduced(get_config(arch))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=8, mean_new=6, seed=6)
    n = 5

    def served(eng, ids):
        eng.reset()
        eng.run(params, [gen.request(i) for i in ids])
        return {r.req_id: r.output for r in eng.done}

    together = ServeEngine(cfg, batch=2, max_seq=32, prefill_len=8,
                           instrument=False)
    alone = ServeEngine(cfg, batch=1, max_seq=32, prefill_len=8,
                        instrument=False)
    want = {}
    for i in range(n):
        want.update(served(alone, [i]))
    got = served(together, range(n))
    assert together.kinds_log.count("prefill") == n   # 5 inserts, 2 slots
    assert got == want and len(got) == n


def test_snapshot_restore_resumes_identically(setup):
    cfg, m, params = setup
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=8, mean_new=10, seed=2)
    reqs = [gen.request(i) for i in range(2)]

    eng = ServeEngine(cfg, batch=2, max_seq=64, prefill_len=8,
                      instrument=False)
    for r in reqs:
        eng.submit(r)
    for _ in range(5):
        eng.step(params)
    snap = eng.snapshot()
    # continue 3 more steps
    for _ in range(3):
        eng.step(params)
    after_direct = np.asarray(eng.last_token).copy()

    # restore the snapshot into a FRESH engine and replay the same 3 steps
    eng2 = ServeEngine(cfg, batch=2, max_seq=64, prefill_len=8,
                       instrument=False)
    for r in reqs:
        eng2.submit(r)
    for _ in range(5):
        eng2.step(params)
    eng2.restore(snap)
    # sync host-side queue state with eng at snapshot time isn't captured;
    # both engines have identical queues here by construction
    for _ in range(3):
        eng2.step(params)
    np.testing.assert_array_equal(after_direct, np.asarray(eng2.last_token))


def _put(cache, row, slot):
    """The eager rule the engine's insert replaced: row 0 of the
    single-row cache into the decode slot, key by key."""
    return {k: (cache[k].at[slot].set(row[k][0]) if k == "length"
                else cache[k].at[:, slot].set(row[k][:, 0]))
            for k in cache}


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "moonlight-16b-a3b",
                                  "mamba2-780m"])
def test_insert_writes_slot_in_place(arch):
    """The insert writes the prefilled row and the first token into the
    first and the last slot of a decode cache filled with noise: every
    key comes out as the eager rule gives, and the decode cache and
    ``last_token`` it was given are donated."""
    cfg = reduced(get_config(arch))
    eng = ServeEngine(cfg, batch=3, max_seq=24, prefill_len=8,
                      instrument=False)
    params = eng.model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), len(eng.cache) + 1))
    eng.cache = {k: (jax.random.randint(next(keys), v.shape, 0, 24, v.dtype)
                     if k == "length"
                     else jax.random.normal(next(keys), v.shape, v.dtype))
                 for k, v in eng.cache.items()}
    eng.last_token = jax.random.randint(next(keys), (3, 1), 0, 100,
                                        np.int32)
    seen = []
    prefill = eng._prefill

    def keep(*a):
        seen.append(prefill(*a))
        return seen[-1]
    eng._prefill = keep
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=8, seed=7)
    for slot in (0, eng.batch - 1):
        # host copies: a view of a buffer would keep it from being donated
        old = jax.tree.map(np.array, eng.cache)
        old_tok = np.array(eng.last_token)
        given = jax.tree.leaves((eng.cache, eng.last_token))
        eng._insert(params, slot, gen.request(slot))
        _, row, _, tok = seen[-1]
        want = _put(jax.tree.map(jax.numpy.asarray, old), row, slot)
        assert all(a.is_deleted() for a in given)
        assert set(eng.cache) == set(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(eng.cache[k]),
                                          np.asarray(want[k]), err_msg=k)
        old_tok[slot] = np.asarray(tok)[0]
        np.testing.assert_array_equal(np.asarray(eng.last_token), old_tok)
        assert eng.slot_req[slot].output == [int(np.asarray(tok)[0, 0])]


def test_insert_compiles_once_for_every_slot(setup):
    """The slot is a traced index: inserts into every slot share one
    compiled program.  Every engine's jit of ``insert_slot`` shares one
    cache, so the count is of what this engine's shapes (used by no other
    test) add to it."""
    cfg, m, params = setup
    eng = ServeEngine(cfg, batch=4, max_seq=40, prefill_len=8,
                      instrument=False)
    gen = SyntheticRequests(cfg.vocab_size, prompt_len=8, seed=8)
    before = eng._insert_slot._cache_size()
    for slot in range(eng.batch):
        eng._insert(params, slot, gen.request(slot))
    assert eng.active.all()
    assert eng._insert_slot._cache_size() - before == 1
