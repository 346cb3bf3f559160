"""Window kind ``serve``: open-loop requests through the program's
``ServeEngine`` (continuous batching over a fixed set of decode slots, its
profiling hooks on), as a user serves a model whose run they profile.

Set-up builds the engine at the cell's ``slots``, ``max_seq`` and the
traffic's ``prompt_len`` (the engine prefills that one length), makes the weights from ``--seed`` in one jitted
call, and warms every shape the window uses: a prefill, a decode of all
slots, and an insert into each slot.  The window offers the traffic's
schedule for ``--seconds`` and steps the engine until every request that
arrived has finished.  ``ttft_p95_ms`` is the 95th percentile, over all of
them, of the time from a request's due time to its first token on the host.

``correct`` compares what the window served with the plain reference: a
sample of the finished requests drawn from the seed, the longest among them,
is run through ``reference.<family>.logits`` in float32 at ``HIGHEST``, and
every served (greedy) token's logit is read against the reference's best at
its position.  ``logit_gap`` is the widest such gap; it has to stay under the
cell's limit.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from bench import arrivals, common

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Served:
    """What the window served: prompts, output tokens and host times."""

    def __init__(self, sched, prompts):
        self.sched = sched                 # [(due_s, max_new_tokens)]
        self.prompts = prompts
        self.first: Dict[int, float] = {}  # req id -> first token (s)
        self.inserted: Dict[int, float] = {}
        self.outputs: Dict[int, List[int]] = {}
        self.prefill_s: List[tuple] = []   # (start s, seconds)
        self.decode_s: List[tuple] = []

    def ttft_s(self) -> np.ndarray:
        return np.array([self.first[i] - due
                         for i, (due, _) in enumerate(self.sched)
                         if i in self.first])


def build(cell):
    import jax
    from repro.serve.engine import ServeEngine
    s = cell.spec
    eng = ServeEngine(cell.program_config(), batch=s["slots"],
                      max_seq=s["max_seq"],
                      prefill_len=cell.traffic["prompt_len"],
                      instrument=True, interval_steps=s["interval_steps"])
    return eng, jax.jit(eng.model.init)


def warm(eng, params, prompts) -> None:
    """One insert into every slot and decodes of all of them, then an
    empty engine."""
    from repro.serve.engine import Request
    eng.run(params, [Request(-1 - i, p, 2) for i, p in enumerate(prompts)])
    eng.reset()


def serve(eng, params, served: Served, seconds: float,
          trace_from: Optional[float] = None,
          record: Optional[Dict] = None) -> float:
    """Offer ``served.sched`` and step the engine until it is drained;
    returns the wall time.  With ``trace_from``, the profiler records from
    that many seconds into the window to the last arrival."""
    from repro.serve.engine import Request
    reqs = [Request(i, p, n) for i, ((_, n), p)
            in enumerate(zip(served.sched, served.prompts))]
    n, nxt = len(reqs), 0
    trace = contextlib.ExitStack()
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if trace_from is not None and now >= trace_from and nxt < n:
            trace.enter_context(common.traced("serve", record))
            trace_from = None
        while nxt < n and served.sched[nxt][0] <= now:
            eng.queue.append(reqs[nxt])
            nxt += 1
        if nxt == n:
            trace.close()
        head = (eng.queue[0] if eng.queue and not eng.active.all()
                else None)
        ts = time.perf_counter()
        with common.span("step"):
            busy = eng.step(params)
        te = time.perf_counter()
        if not busy:
            if nxt == n:
                break
            with common.span("wait"):
                time.sleep(max(0.0, served.sched[nxt][0] - now))
            continue
        if eng.kinds_log[-1] == "prefill":
            served.inserted[head.req_id] = ts - t0
            served.first[head.req_id] = te - t0
            served.prefill_s.append((ts - t0, te - ts))
        else:
            served.decode_s.append((ts - t0, te - ts))
    wall = time.perf_counter() - t0
    trace.close()
    for r in eng.done:
        served.outputs[r.req_id] = list(r.output)
    return wall


def sample(served: Served, seed: int, k: int) -> List[int]:
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    ids = sorted(served.outputs, key=lambda i: (-len(served.outputs[i]), i))
    rest = ids[1:]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [ids[0]] + sorted(rest[int(j)] for j in pick)


def reference_gaps(cell, seed: int, served: Served, ids: List[int],
                   control: bool = False) -> np.ndarray:
    """Per served token of requests ``ids``: the reference's best logit at
    its position minus the reference's logit of the token.  With
    ``control``, the token read is the one the reference computed in fp8
    puts first, not the served one."""
    import jax
    import jax.numpy as jnp
    from bench.reference import train
    ref = cell.reference()
    p_len = cell.traffic["prompt_len"]
    n_out = cell.traffic["output_len"]["max"] + 1
    toks = np.zeros((len(ids), p_len + n_out - 1), np.int32)
    outs = np.zeros((len(ids), n_out), np.int32)
    used = np.zeros((len(ids), n_out), bool)
    for r, i in enumerate(ids):
        o = served.outputs[i]
        toks[r, :p_len] = served.prompts[i]
        toks[r, p_len:p_len + len(o) - 1] = o[:-1]
        outs[r, :len(o)] = o
        used[r, :len(o)] = True
    conf = cell.config
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: train.init_params(ref.param_specs(conf),
                                                     k))(
            jax.random.PRNGKey(seed))

        def logits(precision):
            return jax.jit(lambda p, t: ref.logits(
                p, t, conf, train.matmul(precision), p_len - 1))(
                    params, jnp.asarray(toks))

        want = logits("f32")
        if control:
            outs = np.asarray(jnp.argmax(logits("fp8"), axis=-1))
        gap = jnp.max(want, -1) - jnp.take_along_axis(
            want, jnp.asarray(outs)[..., None], -1)[..., 0]
        gap = np.asarray(gap)
    del params, want
    return gap[used]


def checks(cell, gap: np.ndarray) -> Dict:
    """The numbers compared for ``correct``, each beside the cell's limit:
    ``gap`` is what :func:`reference_gaps` read."""
    return {"logit_gap": {
        "value": float(gap.max()) if gap.size else float("inf"),
        "limit": cell.spec["limits"]["logit_gap"]}}


def run(cell, seed: int, seconds: float, trace: bool) -> Dict:
    import jax
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append(time.perf_counter())
        if name == COMPILE_EVENT else None)
    t = cell.traffic
    eng, init = build(cell)
    params = init(jax.random.PRNGKey(seed))
    sched = arrivals.schedule(t, seconds)
    slots = cell.spec["slots"]
    prompts = arrivals.prompts(t, cell.config["vocab_size"], seed,
                               len(sched) + slots + 1)
    warm(eng, params, prompts[len(sched):])
    served = Served(sched, prompts[:len(sched)])
    record: Dict = {"traces": {}}
    trace_s = cell.spec["trace_seconds"]
    window_start = time.perf_counter()
    wall = serve(eng, params, served, seconds,
                 trace_from=max(0.0, seconds - trace_s) if trace else None,
                 record=record)
    window_compiles = sum(1 for c in compiles if c >= window_start)
    peak = common.memory_peak_bytes()
    eng.profile()
    del eng, params
    gc.collect()

    ids = sample(served, seed, cell.spec["sample_requests"])
    gap = reference_gaps(cell, seed, served, ids)
    ttft = served.ttft_s()
    # host timings of the part of the window the profiler did not slow
    untraced = (seconds - trace_s) if trace else float("inf")
    record.update({
        "queue_wait_s": [served.inserted[i] - due
                         for i, (due, _) in enumerate(sched)
                         if served.inserted.get(i, untraced) < untraced],
        "prefill_s": [d for t0, d in served.prefill_s if t0 < untraced],
        "decode_s": [d for t0, d in served.decode_s if t0 < untraced],
    })
    finished = sum(1 for i, (_, n) in enumerate(sched)
                   if len(served.outputs.get(i, [])) == n + 1)
    return {
        "window_start": window_start,
        "end_to_end": {"ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3
                       if len(ttft) else float("nan")},
        "record": record,
        "attempted": len(sched),
        "failed": len(sched) - finished,
        "memory_peak_bytes": peak,
        "checks": checks(cell, gap),
        "notes": {"compiles_in_window": window_compiles,
                  "served_tokens_compared": int(gap.size),
                  "requests_compared": len(ids)},
    }
