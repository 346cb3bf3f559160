"""Serving engine: continuous batching over a fixed-shape decode batch.

Requests prefill into a single-row cache (fixed prefill length, padded),
built inside the prefill program, and are inserted into a free decode slot
by one program that writes the row into the donated decode cache in place;
every engine iteration decodes the full batch (inactive slots masked).  The
engine is a *profiled program*: prefill and decode iterations emit different
hook streams (merged BlockTable), so serving intervals genuinely vary in
composition — the serving analogue of the paper's multi-phase workloads.
``snapshot()``/``restore()`` capture engine state for replay resets and
elastic migration.  Each iteration is a ``serve.step`` span with a span per
phase inside it (see ``docs/observability.md``).  For an expert model the
step's router counts come back in the step's one blocking read: they become
attributes of the step's ``serve.prefill`` / ``serve.decode`` span,
``serve.*`` counters and the dynamic signature entries of the step's
interval.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.blocks_lm import build_block_table
from repro.core.intervals import IntervalBuilder, Profile
from repro.core.registry import BlockTable, merge_tables
from repro.models.model_zoo import Model, build_model
from repro.serve.sampler import greedy, sample


# the router counts of an expert model's step (models.moe aux), read in the
# step's one device-to-host transfer
MOE_STATS = ("expert_tokens", "held_tokens", "dropped_tokens")
# the entries the interval builder's virtual expert blocks read
MOE_DYN = ("expert_tokens", "dropped_tokens")


def moe_stats(aux: Dict[str, Any]) -> Dict[str, Any]:
    return {k: aux[k] for k in MOE_STATS if k in aux}


def insert_slot(cache: Dict[str, jax.Array], row: Dict[str, jax.Array],
                slot: jax.Array, last_token: jax.Array, tok: jax.Array):
    """Write the single-row cache ``row`` into decode slot ``slot`` of
    ``cache`` (stacked keys at axis 1, ``length`` at axis 0), and the first
    token ``tok`` [1, 1] into ``last_token``'s slot.  Jitted with ``cache``
    donated, the slot's rows are written in place."""
    return ({k: lax.dynamic_update_index_in_dim(
                cache[k], row[k], slot, 0 if k == "length" else 1)
             for k in cache},
            lax.dynamic_update_index_in_dim(last_token, tok, slot, 0))


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: np.ndarray          # [P] int32
    max_new_tokens: int
    submitted_at: float = 0.0
    output: Optional[List[int]] = None
    finished_at: float = 0.0


class SyntheticRequests:
    """Deterministic request stream (stateless in arrival index)."""

    def __init__(self, vocab: int, *, prompt_len: int = 32,
                 mean_new: int = 24, seed: int = 0):
        self.vocab, self.prompt_len, self.mean_new, self.seed = \
            vocab, prompt_len, mean_new, seed

    def request(self, i: int) -> Request:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, i]))
        p = rng.integers(0, self.vocab, size=self.prompt_len).astype(np.int32)
        n = int(rng.integers(self.mean_new // 2, self.mean_new * 2))
        return Request(i, p, n)


class ServeEngine:
    def __init__(self, cfg: ArchConfig, *, batch: int = 4, max_seq: int = 128,
                 prefill_len: int = 32, seed: int = 0,
                 temperature: float = 0.0, instrument: bool = True,
                 interval_steps: float = 4.0,
                 defer_analysis: bool = True):
        self.cfg = cfg
        self.model: Model = build_model(cfg)
        self.batch, self.max_seq, self.prefill_len = batch, max_seq, prefill_len
        self.temperature = temperature
        self.rng = jax.random.PRNGKey(seed)

        self._prefill = jax.jit(self._prefill_row)
        self._insert_slot = jax.jit(insert_slot, donate_argnums=(0, 3))
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(2,))

        self.table: Optional[BlockTable] = None
        self.builder: Optional[IntervalBuilder] = None
        if instrument:
            # FLOP-weighted unit of work: serving steps are heterogeneous in
            # tensor volume (prefill vs decode), see build_block_table docs
            tp = build_block_table(
                self.model, ShapeConfig("p", "prefill", prefill_len, 1),
                train=False, unit="flops")
            td = build_block_table(
                self.model, ShapeConfig("d", "decode", max_seq, batch),
                train=False, unit="flops")
            self.table = merge_tables({"prefill": tp, "decode": td})
            iu = interval_steps * self.table.step_uow("decode")
            # defer_analysis=True (the default) only logs (kind, dyn) per
            # step and runs the vectorized batch analysis once at
            # profile(); False = legacy per-step replay
            self.builder = IntervalBuilder(self.table, iu,
                                           defer=defer_analysis)

        self.reset()

    # ------------------------------------------------------------------
    def reset(self):
        self.cache = self.model.init_cache(self.batch, self.max_seq)
        self.active = np.zeros(self.batch, bool)
        self.remaining = np.zeros(self.batch, np.int64)
        self.slot_req: List[Optional[Request]] = [None] * self.batch
        self.last_token = jnp.zeros((self.batch, 1), jnp.int32)
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self.iterations = 0
        self.kinds_log: List[str] = []

    def submit(self, req: Request):
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    # ------------------------------------------------------------------
    def _admit(self, free: List[int]) -> Tuple[int, Request]:
        with obs.span("serve.admit") as sp:
            req, slot = self.queue.pop(0), free[0]
            sp.set(req=req.req_id, slot=slot)
            if req.submitted_at:        # set by submit(), not by a bare append
                sp.set(queued_s=time.perf_counter() - req.submitted_at)
        return slot, req

    def _prefill_row(self, params, batch):
        """The prefill of one request into a single-row cache made here;
        returns the logits, the row's cache, the aux and the greedy first
        token [1, 1]."""
        row = self.model.init_cache(1, self.max_seq)
        logits, row, aux = self.model.prefill(params, batch, row)
        return logits, row, aux, greedy(logits)

    def _insert(self, params, slot: int, req: Request):
        with obs.span("serve.prefill", req=req.req_id) as psp:
            p = np.zeros(self.prefill_len, np.int32)
            n = min(len(req.prompt), self.prefill_len)
            p[:n] = req.prompt[:n]
            batch = {"tokens": jnp.asarray(p)[None]}
            if self.cfg.family == "encdec":
                batch["frames"] = jnp.zeros((1, self.cfg.n_frames,
                                             self.cfg.d_model), jnp.float32)
            if self.cfg.n_patches:
                batch["patches"] = jnp.zeros((1, self.cfg.n_patches,
                                              self.cfg.d_model), jnp.float32)
            _, row, aux, tok = self._prefill(params, batch)
        with obs.span("serve.insert", req=req.req_id, slot=slot):
            self.cache, self.last_token = self._insert_slot(
                self.cache, row, np.int32(slot), self.last_token, tok)
        with obs.span("serve.read_first", req=req.req_id):
            first, moe = jax.device_get((tok, moe_stats(aux)))
        self.active[slot] = True
        self.remaining[slot] = req.max_new_tokens
        req.output = [int(first[0, 0])]
        self.slot_req[slot] = req
        self._log_step("prefill", self._expert_load(psp, moe, "prefill"))
        obs.metrics().count("serve.prefill_iters")

    def _decode_all(self, params):
        with obs.span("serve.decode", batch=int(self.active.sum())) as dsp:
            self.rng, sub = jax.random.split(self.rng)
            logits, self.cache, aux = self._decode(params, self.last_token,
                                                   self.cache)
            if self.temperature > 0:
                tok = sample(logits, sub, temperature=self.temperature)
            else:
                tok = greedy(logits)
            self.last_token = tok
        with obs.span("serve.read_tokens"):
            # one blocking read of every slot's token and cache length (and
            # an expert model's router counts), all outputs of this decode;
            # the cache is read before the next decode donates it
            toks, lens, moe = jax.device_get(
                (tok, self.cache["length"], moe_stats(aux)))
        with obs.span("serve.retire") as sp:
            done = []
            for b in range(self.batch):
                if not self.active[b]:
                    continue
                req = self.slot_req[b]
                req.output.append(int(toks[b, 0]))
                self.remaining[b] -= 1
                if self.remaining[b] <= 0 or lens[b] >= self.max_seq:
                    req.finished_at = time.perf_counter()
                    self.done.append(req)
                    done.append(req.req_id)
                    self.active[b] = False
                    self.slot_req[b] = None
            sp.set(done=done)
        self._log_step("decode", self._expert_load(dsp, moe, "decode"))
        obs.metrics().count("serve.decode_iters")

    def _expert_load(self, span, moe: Dict[str, Any],
                     kind: str) -> Optional[Dict[str, Any]]:
        """An expert model's router counts of one step (host arrays, summed
        over its expert layers): ``held_tokens`` (token-expert pairs routed
        to the experts held here) and ``expert_load_max`` (the busiest held
        expert's pairs) go onto the step's ``serve.prefill`` /
        ``serve.decode`` ``span``, which has closed by the time they are
        read, and into the ``serve.*`` counters; returns the interval
        builder's dynamic entries (None for a model without experts)."""
        if "expert_tokens" not in moe:
            return None
        m = self.cfg.moe
        load = moe["expert_tokens"][m.held_first:m.held_first + m.n_local]
        held = int(moe.get("held_tokens", load.sum()))
        load_max = int(load.max())
        span.set(held_tokens=held, expert_load_max=load_max)
        c = obs.metrics()
        c.count(f"serve.held_tokens.{kind}", held)
        c.count(f"serve.expert_load_max.{kind}", load_max)
        c.count("serve.dropped_tokens", int(moe["dropped_tokens"]))
        return {k: moe[k] for k in MOE_DYN}

    def _log_step(self, kind: str,
                  dyn: Optional[Dict[str, Any]] = None) -> None:
        if self.builder is not None:
            with obs.span("serve.meter"):
                self.builder.add_step(kind=kind, dyn=dyn)
        self.kinds_log.append(kind)
        self.iterations += 1

    # ------------------------------------------------------------------
    def step(self, params) -> bool:
        """One engine iteration.  Returns False when idle.  The engine keeps
        no reference to ``params`` after it returns."""
        free = [b for b in range(self.batch) if not self.active[b]]
        if free and self.queue:
            with obs.span("serve.step", kind="insert"):
                self._insert(params, *self._admit(free))
            return True
        if self.active.any():
            with obs.span("serve.step", kind="decode"):
                self._decode_all(params)
            return True
        return False

    def run(self, params, requests: List[Request]) -> Dict[str, float]:
        for r in requests:
            self.submit(r)
        t0 = time.perf_counter()
        with obs.span("serve.run", requests=len(requests)):
            while self.step(params):
                pass
            jax.block_until_ready(self.last_token)
        wall = time.perf_counter() - t0
        toks = sum(len(r.output or []) for r in self.done)
        lat = [r.finished_at - r.submitted_at for r in self.done
               if r.finished_at]
        m = obs.metrics()
        m.count("serve.requests", len(self.done))
        m.count("serve.tokens", toks)
        m.record("serve.tokens_per_s", toks / max(wall, 1e-9))
        for v in lat:
            m.observe("serve.latency_s", v)
        return {
            "wall_s": wall,
            "tokens": toks,
            "tokens_per_s": toks / max(wall, 1e-9),
            "requests": len(self.done),
            "mean_latency_s": float(np.mean(lat)) if lat else 0.0,
            "iterations": self.iterations,
        }

    # ------------------------------------------------------------------
    def profile(self) -> Profile:
        assert self.builder is not None
        with obs.span("serve.profile_finalize"):
            return self.builder.finalize()

    def snapshot(self) -> Dict[str, Any]:
        """Host-memory engine state (elastic migration / replay resets)."""
        return {
            "cache": jax.tree.map(np.asarray, self.cache),
            "active": self.active.copy(),
            "remaining": self.remaining.copy(),
            "last_token": np.asarray(self.last_token),
            "iterations": self.iterations,
        }

    def restore(self, snap: Dict[str, Any]):
        self.cache = jax.tree.map(jnp.asarray, snap["cache"])
        self.active = snap["active"].copy()
        self.remaining = snap["remaining"].copy()
        self.last_token = jnp.asarray(snap["last_token"])
        self.iterations = snap["iterations"]
