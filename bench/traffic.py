"""The one traffic generator: a mix is a JSON file of parameters.

A mix file under ``bench/traffic/<name>.json`` says

- ``loop``: ``"open"`` (requests due on a schedule, ``rate_rps``) or
  ``"closed"`` (``clients`` callers, each sending its next request when
  its last one finishes);
- ``prompt`` and ``output``: length distributions, each
  ``{"median", "sigma", "min", "max"}`` of a lognormal, clipped;
- ``warm_s``: seconds served before the window opens and not counted.
  An open loop's arrivals run through them at its rate; a closed loop's
  clients all send their first request when they start;
- ``window_requests`` (closed loop): how many requests the window's set
  holds; the clients take them in order;
- ``prime`` (optional): requests already in flight when the warm-up
  starts, as in a server that has carried the mix for a long time, so
  that the window opens on a loaded engine rather than an empty one.
  An open loop sends ``prime.requests`` of them at once; a closed loop's
  first request of each client is one. A prime's prompt is the mix's,
  clipped to ``prime.prompt_max`` tokens (its prefill only fills the
  cache before the window), and its answer is drawn from what is left
  of an answer caught part-way: the residual of the output distribution,
  in which an answer of n tokens is n times as likely to be caught as
  one of 1 token, and caught at any of its tokens alike.

Every seed gets the same set of lengths and, for an open loop, the same
set of gaps between arrivals: each is the stratified quantiles of its
distribution. The seed only orders them and draws the token ids, so two
seeds ask the system for the same work in a different order.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics
from typing import List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Req:
    """One request as generated: ``gap_s`` is its arrival's distance from
    the one before it (open loop), ``prompt`` its token ids and
    ``max_new`` the number of tokens it asks for."""

    gap_s: float
    prompt: np.ndarray
    max_new: int


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    params: dict

    @property
    def loop(self) -> str:
        return self.params["loop"]


def load(name: str, directory: pathlib.Path = HERE / "traffic") -> Mix:
    path = directory / f"{name}.json"
    params = json.loads(path.read_text())
    loop = params.get("loop")
    if loop not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed', not {loop!r}")
    need = {"open": ("rate_rps",), "closed": ("clients", "window_requests")}
    for key in ("prompt", "output", "warm_s") + need[loop]:
        if key not in params:
            raise ValueError(f"{path}: missing {key!r}")
    prime = params.get("prime")
    if prime is not None:
        want = ("prompt_max", "requests") if loop == "open" else ("prompt_max",)
        for key in want:
            if key not in prime:
                raise ValueError(f"{path}: prime is missing {key!r}")
    return Mix(name, params)


def stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal, as whole
    numbers: the i-th is the quantile at (i + 0.5) / n."""
    z = statistics.NormalDist()
    out = [spec["median"] * math.exp(spec["sigma"] * z.inv_cdf(q))
           for q in stratified(n)]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def residual_quantiles(spec: dict, n: int, grid: int = 4096) -> np.ndarray:
    """``n`` stratified quantiles of the tokens left of an answer caught
    part-way (see the module docstring): P(r left) is proportional to
    P(length >= r), r = 1 .. the longest answer."""
    lengths = lognormal_quantiles(spec, grid)
    at_least = np.cumsum(np.bincount(lengths)[::-1])[::-1]  # [r]: # >= r
    cdf = np.cumsum(at_least[1:]) / at_least[1:].sum()
    return 1 + np.searchsorted(cdf, stratified(n))


def exponential_quantiles(rate: float, n: int) -> np.ndarray:
    return np.array([-math.log(1.0 - q) / rate for q in stratified(n)])


def phase_size(mix: Mix, seconds: float) -> int:
    """Requests in a phase of ``seconds``: the expected arrivals of an
    open loop, or the closed loop's fixed window set."""
    if mix.loop == "open":
        return max(1, int(round(mix.params["rate_rps"] * seconds)))
    return int(mix.params["window_requests"])


def _make(prompts, outputs, gaps, rng, vocab) -> List[Req]:
    return [Req(float(g), rng.integers(1, vocab, size=int(p), dtype=np.int32),
                int(o)) for g, p, o in zip(gaps, prompts, outputs)]


def requests(mix: Mix, n: int, rng: np.random.Generator,
             vocab: int) -> List[Req]:
    """The phase's ``n`` requests in the order ``rng`` gives them."""
    prompts = rng.permutation(lognormal_quantiles(mix.params["prompt"], n))
    outputs = rng.permutation(lognormal_quantiles(mix.params["output"], n))
    if mix.loop == "open":
        gaps = rng.permutation(exponential_quantiles(mix.params["rate_rps"], n))
    else:
        gaps = np.zeros(n)
    return _make(prompts, outputs, gaps, rng, vocab)


def primes(mix: Mix, n: int, rng: np.random.Generator,
           vocab: int) -> List[Req]:
    """``n`` requests in flight at the start (see ``prime``), all due at
    once."""
    cap = int(mix.params["prime"]["prompt_max"])
    prompts = rng.permutation(np.minimum(
        lognormal_quantiles(mix.params["prompt"], n), cap))
    outputs = rng.permutation(residual_quantiles(mix.params["output"], n))
    return _make(prompts, outputs, np.zeros(n), rng, vocab)


def phases(mix: Mix, seed: int, seconds: float, vocab: int):
    """(warm-up requests, window requests) for ``seed``. The warm-up's
    are the primes, then, for an open loop, ``warm_s`` seconds of
    arrivals; a closed loop's are each client's first request. The
    window's are made the same way for the measured ``seconds``."""
    rng = np.random.default_rng([seed, 0x7261])
    p = mix.params
    if mix.loop == "closed":
        warm = (primes if "prime" in p else requests)(
            mix, int(p["clients"]), rng, vocab)
    else:
        warm = (primes(mix, int(p["prime"]["requests"]), rng, vocab)
                if "prime" in p else [])
        warm += requests(mix, phase_size(mix, p["warm_s"]), rng, vocab)
    window = requests(mix, phase_size(mix, seconds), rng, vocab)
    return warm, window
