"""The serving engine over the shard_map'd SPMD programs.

One engine, :class:`PagedEngine`, over a block/paged KV cache
(serve/kvcache.py), a dedicated chunked-prefill program that writes
straight into the page pool, and continuous batching with mixed
prefill+decode scheduling under a token budget (serve/scheduler.py).
Prefill and decode are separate compiled programs and may carry
separate overlap policies (prefill resolves ag_matmul/matmul_rs in the
chunk projections; decode resolves flash_decode/a2a_ep). Drivers step
it through ``add / can_accept / step / leftover`` (see
:func:`repro.serve.load.drive`).

Serving metrics: the engine keeps the standard latency/occupancy
counters as it runs — TTFT (arrival -> first generated token), TPOT
(mean seconds per output token after the first), queue depth and slot
occupancy sampled per step, prefill-vs-decode step split — reduced into
a :class:`Metrics` snapshot via ``metrics()``.

Tracing: ``PagedEngine.step`` marks its phases with host spans
(``serve.step``, ``serve.schedule``, ``serve.prefill``, ``serve.decode``
and their ``.launch`` / ``.fetch`` / ``.sample``), with counters; see
:mod:`repro.obs` for the table. A profiler capture
(``jax.profiler.trace``) records them; without one they cost about a
microsecond each.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List

import jax.numpy as jnp
import numpy as np

from .. import obs
from .kvcache import PagedKVCache
from .scheduler import Scheduler, ServeConfig


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False  # clipped by cache capacity, not eos/max_new
    # serving-metrics timestamps (time.perf_counter seconds)
    t_arrive: float = 0.0   # stamped by PagedEngine.add
    t_first: float = 0.0    # first generated (non-prompt) token
    t_done: float = 0.0     # request completion


@dataclasses.dataclass(frozen=True)
class Metrics:
    """Aggregate serving metrics for one engine run."""

    requests_completed: int
    tokens_generated: int       # output tokens across completed + live
    steps: int                  # engine steps executed (prefill + decode)
    ttft_mean_s: float          # arrival -> first token, mean (completed)
    ttft_max_s: float
    tpot_mean_s: float          # per-output-token seconds after the first
    queue_depth_mean: float     # pending requests, sampled per step
    queue_depth_max: int
    slot_occupancy_mean: float  # occupied batch slots / batch, per step
    steps_prefill: int = 0      # chunked-prefill program calls
    steps_decode: int = 0       # decode program calls
    requests_truncated: int = 0  # finished by capacity, not eos/max_new

    def __str__(self) -> str:
        return (f"Metrics(completed={self.requests_completed} "
                f"tokens={self.tokens_generated} steps={self.steps} "
                f"(prefill {self.steps_prefill} decode {self.steps_decode}) "
                f"truncated={self.requests_truncated} "
                f"ttft={self.ttft_mean_s * 1e3:.1f}ms "
                f"(max {self.ttft_max_s * 1e3:.1f}ms) "
                f"tpot={self.tpot_mean_s * 1e3:.2f}ms "
                f"queue={self.queue_depth_mean:.2f} "
                f"(max {self.queue_depth_max}) "
                f"occupancy={self.slot_occupancy_mean:.2f})")


def _sample_row(rng, row: np.ndarray, temperature: float) -> int:
    if temperature <= 0:
        return int(np.argmax(row))
    p = np.exp((row - row.max()) / temperature)
    p /= p.sum()
    return int(rng.choice(len(row), p=p))


def _describe(policy, op: str) -> str:
    """'mode/backend[/xN]/wire' — the wire dtype is always explicit so
    the wire axis shows up in serve provenance."""
    r = policy.resolve(op)
    desc = f"{r.mode}/{r.backend}"
    if r.chunks > 1:
        desc += f"/x{r.chunks}"
    return desc + f"/{r.wire}"


class PagedEngine:
    """Continuous-batching engine over the paged KV pools.

    prefill_fn(params, pools, table_rows, starts, n_valids, tokens)
        -> (logits (n_streams, vocab), pools)
    decode_fn(params, pools, table, lengths, active, token)
        -> (logits (batch, vocab), pools)
    — the two jit(shard_map(...)) programs built by the launcher
    (launch/steps.py build_prefill_chunk_step / build_paged_decode_step).
    """

    # ops resolved by each phase's compiled program (context-parallel
    # prefill additionally resolves the placement-aware ring_attention)
    PHASE_OPS = {"prefill": ("ag_matmul", "matmul_rs"),
                 "decode": ("a2a_ep", "flash_decode")}

    def __init__(
        self,
        prefill_fn: Callable,
        decode_fn: Callable,
        params,
        init_pools,
        scfg: ServeConfig,
        *,
        dp_shards: int = 1,
        eos_id: int = -1,
        seed: int = 0,
        pcfg=None,          # decode-phase ParallelConfig (provenance)
        prefill_pcfg=None,  # prefill-phase ParallelConfig; defaults to pcfg
        prefill_cp: bool = False,
        cp_placement: str = "zigzag",
    ):
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.params = params
        self.pools = init_pools
        self.scfg = scfg
        self.dp_shards = dp_shards
        self.eos_id = eos_id
        self.pcfg = pcfg
        self.prefill_pcfg = prefill_pcfg if prefill_pcfg is not None else pcfg
        self.prefill_cp = prefill_cp
        self.cp_placement = cp_placement
        if prefill_cp:
            self.PHASE_OPS = dict(self.PHASE_OPS)
            self.PHASE_OPS["prefill"] = (
                self.PHASE_OPS["prefill"] + ("ring_attention",))
        self.kv = PagedKVCache(
            batch=scfg.batch, max_len=scfg.max_len, page_size=scfg.page_size,
            num_pages=scfg.num_pages, dp_shards=dp_shards)
        self.sched = Scheduler(scfg, self.kv, dp_shards)
        self.rng = np.random.RandomState(seed)
        self._steps = 0
        self._steps_prefill = 0
        self._steps_decode = 0
        self._completed = 0
        self._truncated = 0
        self._tokens_completed = 0
        self._ttfts: List[float] = []
        self._tpots: List[float] = []
        # per-step samples of queue depth and slot occupancy, kept as
        # running sums (and the deepest queue) for the server's lifetime
        self._queue_sum = 0
        self._queue_max = 0
        self._occ_sum = 0.0

    def overlap_modes(self) -> dict:
        """Per-PHASE overlap provenance: 'phase:op' ->
        'mode/backend[/xN]/wire' — prefill and decode are separate
        compiled programs and may resolve through separate policies."""
        if self.pcfg is None:
            return {}
        out = {}
        for phase, ops_ in self.PHASE_OPS.items():
            pcfg = self.prefill_pcfg if phase == "prefill" else self.pcfg
            for op in ops_:
                row = _describe(pcfg.policy, op)
                # the CP prefill's placement is a step-level knob (threaded
                # straight into the placed op, not via the policy) — report
                # it where the policy would have (contiguous stays implied)
                if (phase == "prefill" and op == "ring_attention"
                        and self.prefill_cp
                        and self.cp_placement != "contiguous"
                        and not row.endswith(("/zigzag", "/striped"))):
                    row += f"/{self.cp_placement}"
                out[f"{phase}:{op}"] = row
        return out

    def leftover(self) -> List[Request]:
        """Requests still queued or in a slot."""
        live = [s.req for s in self.sched.slots if s.req is not None]
        return list(self.sched.queue) + live

    # ------------------------------------------------------------------
    def add(self, req: Request) -> bool:
        """Submit to the bounded queue; False = backpressure (caller
        retries after draining)."""
        req.t_arrive = time.perf_counter()
        return self.sched.submit(req)

    def can_accept(self) -> bool:
        return self.sched.queue_depth() < self.scfg.queue_cap

    # ------------------------------------------------------------------
    def _sample_load(self, queue: int, occupancy: float) -> None:
        self._queue_sum += queue
        self._queue_max = max(self._queue_max, queue)
        self._occ_sum += occupancy

    def _finish(self, req: Request, now: float) -> None:
        req.done = True
        req.t_done = now
        self._completed += 1
        self._truncated += bool(req.truncated)
        self._tokens_completed += len(req.out_tokens)
        if req.t_first:
            self._ttfts.append(req.t_first - req.t_arrive)
            if len(req.out_tokens) > 1:
                self._tpots.append((req.t_done - req.t_first)
                                   / (len(req.out_tokens) - 1))

    def _emit(self, slot_id: int, tok: int, now: float) -> None:
        """Record one generated token for the slot's request; finish +
        release the slot on eos / max_new / capacity."""
        s = self.sched.slots[slot_id]
        req = s.req
        if not req.out_tokens:
            req.t_first = now
        req.out_tokens.append(tok)
        s.last_token = tok
        limit = min(req.max_new_tokens, s.gen_budget)
        if tok == self.eos_id or len(req.out_tokens) >= limit:
            if (tok != self.eos_id
                    and len(req.out_tokens) < req.max_new_tokens):
                req.truncated = True  # out of KV capacity, not finished
            self._finish(req, now)
            self.sched.release(slot_id)

    def _prefill_step(self, items) -> None:
        """Run one chunked-prefill program call covering <= 1 chunk per
        DP shard; a prompt-completing chunk's logits carry the request's
        FIRST generated token (TTFT stamps here, not at first decode)."""
        n_streams = self.dp_shards
        p = self.kv.pages_per_slot
        c = self.scfg.chunk
        with obs.span("serve.prefill.launch"):
            table = np.zeros((n_streams, p), np.int32)
            starts = np.zeros((n_streams,), np.int32)
            nvalid = np.zeros((n_streams,), np.int32)
            toks = np.zeros((n_streams, c), np.int32)
            for slot_id, start, n in items:
                sh = self.kv.shard(slot_id)
                table[sh] = self.kv.table[slot_id]
                starts[sh] = start
                nvalid[sh] = n
                toks[sh, :n] = self.sched.slots[slot_id].req.prompt[start:start + n]
            logits, self.pools = self.prefill_fn(
                self.params, self.pools, jnp.asarray(table),
                jnp.asarray(starts), jnp.asarray(nvalid), jnp.asarray(toks))
        self._steps_prefill += 1
        with obs.span("serve.prefill.fetch"):
            logits = np.asarray(logits)
        now = time.perf_counter()
        with obs.span("serve.prefill.sample"):
            for slot_id, start, n in items:
                s = self.sched.slots[slot_id]
                if self.sched.note_chunk(slot_id, n):
                    tok = _sample_row(self.rng, logits[self.kv.shard(slot_id)],
                                      s.req.temperature)
                    self._emit(slot_id, tok, now)

    def _decode_step(self, slot_ids) -> None:
        b = self.scfg.batch
        with obs.span("serve.decode.launch"):
            toks = np.zeros((b, 1), np.int32)
            active = np.zeros((b,), bool)
            for i in slot_ids:
                toks[i, 0] = self.sched.slots[i].last_token
                active[i] = True
            logits, self.pools = self.decode_fn(
                self.params, self.pools, jnp.asarray(self.kv.table),
                jnp.asarray(self.kv.lens), jnp.asarray(active),
                jnp.asarray(toks))
        self._steps_decode += 1
        with obs.span("serve.decode.fetch"):
            logits = np.asarray(logits)
        now = time.perf_counter()
        with obs.span("serve.decode.sample"):
            for i in slot_ids:
                s = self.sched.slots[i]
                self.sched.note_decode(i)
                tok = _sample_row(self.rng, logits[i], s.req.temperature)
                self._emit(i, tok, now)

    def step(self) -> bool:
        """One scheduler iteration: admit, plan one mixed prefill+decode
        batch under the token budget, execute. False when idle."""
        if self.sched.idle():  # admission cannot make an idle engine busy
            return False
        with obs.span("serve.step") as step_span:
            with obs.span("serve.schedule"):
                self.sched.admit()
                plan = self.sched.plan()
            queue, slots = self.sched.queue_depth(), self.sched.busy_slots()
            step_span.set_metadata(queue=queue, slots=slots,
                                   pages=self.kv.pages_held())
            self._sample_load(queue, slots / self.scfg.batch)
            if plan.prefill:
                with obs.span("serve.prefill", streams=len(plan.prefill),
                              tokens=sum(n for _, _, n in plan.prefill)):
                    self._prefill_step(plan.prefill)
            if plan.decode:
                lens = self.kv.lens[plan.decode]
                # pages: what the decode kernel reads, each slot's cached
                # tokens and the new one
                with obs.span("serve.decode", slots=len(plan.decode),
                              context=int(lens.sum()),
                              pages=int((lens // self.kv.page_size + 1)
                                        .sum())):
                    self._decode_step(plan.decode)
            self._steps += 1
        return True

    def metrics(self) -> Metrics:
        """Snapshot of the run's serving metrics."""
        n_steps = max(1, self._steps)
        tokens = self._tokens_completed
        tokens += sum(len(r.out_tokens) for r in self.leftover())
        return Metrics(
            requests_completed=self._completed,
            tokens_generated=tokens,
            steps=self._steps,
            ttft_mean_s=(sum(self._ttfts) / len(self._ttfts)
                         if self._ttfts else 0.0),
            ttft_max_s=max(self._ttfts, default=0.0),
            tpot_mean_s=(sum(self._tpots) / len(self._tpots)
                         if self._tpots else 0.0),
            queue_depth_mean=self._queue_sum / n_steps,
            queue_depth_max=self._queue_max,
            slot_occupancy_mean=self._occ_sum / n_steps,
            steps_prefill=self._steps_prefill,
            steps_decode=self._steps_decode,
            requests_truncated=self._truncated,
        )

    def run(self, max_steps: int = 10_000):
        """Drive all requests to completion (or max_steps); returns the
        requests still live/pending when the step budget runs out."""
        for _ in range(max_steps):
            if not self.step():
                break
        return self.leftover()
