"""Seeded synthetic request streams + the arrival-aware drive loop.

The generator feeds the serving CLI and the chip smoke run: Poisson
arrivals at ``rate_rps`` with prompt lengths uniform over
``prompt_lens`` and seeded token ids, so every run of a given
(spec, vocab) pair replays the identical stream. :func:`drive` releases
requests by wall clock and steps a :class:`repro.serve.PagedEngine`
(``add / can_accept / step / leftover``), applying backpressure when
the engine's queue is full.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Tuple

import numpy as np

from .engine import Request


@dataclasses.dataclass(frozen=True)
class LoadSpec:
    """One synthetic stream: ``n_requests`` Poisson arrivals."""

    n_requests: int = 64
    rate_rps: float = 32.0              # mean arrival rate (requests/s)
    prompt_lens: Tuple[int, int] = (128, 2048)  # uniform inclusive range
    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0


def generate(spec: LoadSpec, vocab_size: int) -> List[Tuple[float, Request]]:
    """[(arrival_time_s, Request)] sorted by arrival; fully seeded."""
    rng = np.random.RandomState(spec.seed)
    gaps = rng.exponential(1.0 / spec.rate_rps, size=spec.n_requests)
    arrive = np.cumsum(gaps)
    lo, hi = spec.prompt_lens
    lens = rng.randint(lo, hi + 1, size=spec.n_requests)
    out = []
    for t, n in zip(arrive, lens):
        prompt = rng.randint(1, vocab_size, size=int(n)).tolist()
        out.append((float(t), Request(prompt=prompt,
                                      max_new_tokens=spec.max_new_tokens,
                                      temperature=spec.temperature)))
    return out


def drive(engine, arrivals: List[Tuple[float, Request]], *,
          max_steps: int = 1_000_000, time_scale: float = 1.0):
    """Release ``arrivals`` by wall clock (arrival times multiplied by
    ``time_scale`` — 0 releases everything up front, the
    closed-loop/offline regime) and step the engine until all work
    drains or ``max_steps``. Returns the engine's leftover requests."""
    t0 = time.perf_counter()
    i, n = 0, len(arrivals)
    for _ in range(max_steps):
        now = time.perf_counter() - t0
        while (i < n and arrivals[i][0] * time_scale <= now
               and engine.can_accept()):
            engine.add(arrivals[i][1])
            i += 1
        if not engine.step():
            if i >= n:
                break  # drained: no queued, live, or future work
            # idle but arrivals remain: sleep until the next one lands
            time.sleep(min(0.002, max(0.0, arrivals[i][0] * time_scale - now)))
    return engine.leftover()
