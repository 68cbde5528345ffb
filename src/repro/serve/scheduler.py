"""Continuous-batching scheduler: mixed prefill+decode planning.

Each engine step the scheduler (1) ADMITS requests from the bounded
queue into free slots — with whole-request KV page allocation up front
(prompt + max_new_tokens), so an admitted request can never stall
mid-decode for pages; (2) PLANS one mixed batch under the
``token_budget`` knob: every decoding slot contributes one token, and
the remaining budget is filled with prefill chunks — at most one per DP
shard per step, because the chunked-prefill program runs one request
stream per data rank.

Everything is deterministic by construction (FIFO queue, lowest-fitting-
slot admission, lowest-slot-first prefill) so tests can pin
hand-computed schedules.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import List, Optional, Tuple

from .kvcache import PagedKVCache


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs of the paged serving engine."""

    batch: int = 8          # decode slots (global batch of the decode program)
    max_len: int = 256      # per-request cap: prompt + generated tokens
    page_size: int = 16     # KV tokens per pool page
    num_pages: int = 0      # pool pages per DP shard; 0 = dense-equivalent
    chunk: int = 32         # prefill chunk length (multiple of tp)
    token_budget: int = 64  # decode tokens + prefill-chunk tokens per step
    queue_cap: int = 256    # bounded admission queue


@dataclasses.dataclass
class Slot:
    req: Optional[object] = None  # serve.engine.Request
    phase: str = "idle"           # idle | prefill | decode
    prompt_len: int = 0           # possibly clipped to fit max_len
    prompt_done: int = 0          # prompt tokens already prefilled
    gen_budget: int = 0           # output tokens this slot may produce
    last_token: int = 0           # decode input for the next step


@dataclasses.dataclass
class Plan:
    """One step's work: decode slot ids + prefill chunks (slot, start, n)
    — the prefill list holds at most one chunk per DP shard."""

    decode: List[int]
    prefill: List[Tuple[int, int, int]]


class Scheduler:
    def __init__(self, scfg: ServeConfig, kv: PagedKVCache, dp_shards: int = 1):
        assert scfg.batch % dp_shards == 0
        self.scfg = scfg
        self.kv = kv
        self.dp_shards = dp_shards
        self.slots_per_shard = scfg.batch // dp_shards
        self.queue: deque = deque()
        self.slots = [Slot() for _ in range(scfg.batch)]

    # ------------------------------------------------------------------
    def submit(self, req) -> bool:
        """Enqueue; False when the bounded queue is full (backpressure)."""
        if len(self.queue) >= self.scfg.queue_cap:
            return False
        self.queue.append(req)
        return True

    def queue_depth(self) -> int:
        return len(self.queue)

    def busy_slots(self) -> int:
        """Slots holding a request."""
        return sum(s.phase != "idle" for s in self.slots)

    def occupancy(self) -> float:
        return self.busy_slots() / self.scfg.batch

    def idle(self) -> bool:
        return not self.queue and all(s.phase == "idle" for s in self.slots)

    # ------------------------------------------------------------------
    def admit(self) -> List[int]:
        """FIFO admission into the lowest free slot whose shard has pages.
        Head-of-line blocking is deliberate: requests are never reordered,
        so scheduling stays deterministic and starvation-free."""
        admitted = []
        while self.queue:
            req = self.queue[0]
            prompt_len = min(len(req.prompt), self.scfg.max_len - 1)
            total = min(prompt_len + req.max_new_tokens, self.scfg.max_len)
            slot_id = None
            for i, s in enumerate(self.slots):
                if s.phase == "idle" and self.kv.can_alloc(i, total):
                    slot_id = i
                    break
            if slot_id is None:
                break
            self.queue.popleft()
            self.kv.alloc(slot_id, total)
            if prompt_len < len(req.prompt):
                req.truncated = True  # prompt clipped to fit the slot
            s = self.slots[slot_id]
            s.req = req
            s.phase = "prefill"
            s.prompt_len = prompt_len
            s.prompt_done = 0
            s.gen_budget = total - prompt_len
            s.last_token = 0
            admitted.append(slot_id)
        return admitted

    def plan(self) -> Plan:
        """Decode slots first (latency priority), then prefill chunks into
        the remaining token budget — at most one chunk per DP shard. One
        chunk always proceeds when nothing is decoding, so the engine
        never stalls on an over-tight budget."""
        decode = [i for i, s in enumerate(self.slots) if s.phase == "decode"]
        room = self.scfg.token_budget - len(decode)
        prefill: List[Tuple[int, int, int]] = []
        used_shards = set()
        for i, s in enumerate(self.slots):
            if s.phase != "prefill":
                continue
            shard = i // self.slots_per_shard
            if shard in used_shards:
                continue
            n = min(self.scfg.chunk, s.prompt_len - s.prompt_done)
            if n > room and (decode or prefill):
                continue
            prefill.append((i, s.prompt_done, n))
            used_shards.add(shard)
            room -= n
        return Plan(decode, prefill)

    # ------------------------------------------------------------------
    # notifications from the engine after it runs a planned step
    def note_chunk(self, slot_id: int, n: int) -> bool:
        """Record ``n`` prefilled prompt tokens; True when the prompt just
        completed (the chunk's logits carry the request's first token)."""
        s = self.slots[slot_id]
        s.prompt_done += n
        self.kv.lens[slot_id] += n
        if s.prompt_done >= s.prompt_len:
            s.phase = "decode"
            return True
        return False

    def note_decode(self, slot_id: int) -> None:
        self.kv.lens[slot_id] += 1

    def release(self, slot_id: int) -> None:
        self.kv.free(slot_id)
        self.slots[slot_id] = Slot()
