"""A routed-expert model served through the paged engine, against the
plain float32 reference of ``bench/reference/model.py``.

The serving programs route dropless: each expert's buffer holds every
token of the call, whatever ``capacity_factor`` says (training keeps the
factor). Both tests record, for every token the engine emits, the row of
logits it was chosen from, and compare it with the reference's logits at
that position of the request's sequence.
"""
import jax
import numpy as np
import pytest

from bench import model as bench_model
from bench.reference import model as ref
from repro.configs import ARCHS, reduced
from repro.configs.base import ParallelConfig
from repro.serve import engine as serve_engine
from repro.serve.engine import Request

PCFG = ParallelConfig(dp=1, tp=1, fsdp=False, compute_dtype="float32",
                      param_dtype="float32", overlap_mode="none")
# Both sides compute in float32 and differ only in summation order (the
# pages' online softmax, the chunk's blocked attention, the experts'
# weighted sum): about 1e-6 of the logits' scale. A dropped token, a
# wrong page, position or expert moves logits by 1e-2 or more.
ATOL = 1e-4


def _spec(cfg) -> dict:
    """The reference's configuration keys for the program's ``cfg``."""
    return {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
            "intermediate_size": cfg.d_ff, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps, "num_local_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.experts_per_token}


def _serve(eng, requests, monkeypatch):
    """Serve ``requests`` to the end; returns, per request, the logits
    row each emitted token was sampled from."""
    rows, pending = {}, []
    sample, emit = serve_engine._sample_row, eng._emit

    def recording_sample(rng, row, temperature):
        pending.append(np.array(row))
        return sample(rng, row, temperature)

    def recording_emit(slot_id, tok, now):
        req = eng.sched.slots[slot_id].req
        rows.setdefault(id(req), []).append(pending.pop())
        emit(slot_id, tok, now)

    monkeypatch.setattr(serve_engine, "_sample_row", recording_sample)
    monkeypatch.setattr(eng, "_emit", recording_emit)
    for r in requests:
        assert eng.add(r)
    assert eng.run() == []
    assert not pending
    return [np.stack(rows[id(r)]) for r in requests]


def _reference(cfg, params, req) -> np.ndarray:
    spec = _spec(cfg)
    tokens = np.concatenate([req.prompt, req.out_tokens[:-1]]).astype(np.int32)
    rows = len(req.prompt) - 1 + np.arange(len(req.out_tokens))
    top = bench_model.unpack_top(params, spec)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(
            tokens, rows, spec,
            lambda i: bench_model.unpack_layer(params["layers"], i, spec), top))


def _engine(cfg, mesh, **serve):
    from repro.launch.serve import build_paged_engine
    from repro.serve import ServeConfig

    return build_paged_engine(cfg, PCFG, ServeConfig(**serve), mesh, seed=3)


def test_served_logits_match_the_reference(one_device_mesh, monkeypatch):
    """Seeded random weights: a prompt over three chunks, a second
    request alongside, then a third that takes a freed slot; every
    emitted token's logits (one from prefill, the rest from paged
    decode steps) match the reference's."""
    cfg = reduced(ARCHS["granite-moe-3b-a800m"])
    eng = _engine(cfg, one_device_mesh, batch=2, max_len=64, page_size=8,
                  chunk=8, token_budget=10)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (21, 6, 9)]
    reqs = [Request(prompt=p, max_new_tokens=n)
            for p, n in zip(prompts, (10, 12, 9))]
    got = _serve(eng, reqs, monkeypatch)
    assert eng.metrics().steps_prefill >= 3 + 1 + 2
    assert [len(r.out_tokens) for r in reqs] == [10, 12, 9]
    for r, rows in zip(reqs, got):
        want = _reference(cfg, eng.params, r)
        np.testing.assert_allclose(rows, want, rtol=0, atol=ATOL)


def test_serving_drops_no_token_when_routing_piles_up(one_device_mesh,
                                                      monkeypatch):
    """The default capacity factor (1.25) with a router of zeros: every
    token ties, so top-2 picks experts 0 and 1 for all of them, twice
    the even share each. Buffers sized by the factor would hold 8 of a
    16-token chunk and 8 of 12 decode lanes; the serving programs drop
    none, so every token's logits still match the reference's."""
    cfg = reduced(ARCHS["granite-moe-3b-a800m"], num_experts=8)
    assert cfg.capacity_factor == 1.25
    eng = _engine(cfg, one_device_mesh, batch=12, max_len=48, page_size=8,
                  chunk=16, token_budget=28)
    ffn = eng.params["layers"]["ffn"]
    eng.params = dict(eng.params, layers=dict(
        eng.params["layers"], ffn=dict(ffn, router=ffn["router"] * 0)))
    rng = np.random.default_rng(11)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                    max_new_tokens=8)
            for n in rng.integers(17, 30, size=12)]
    got = _serve(eng, reqs, monkeypatch)
    for r, rows in zip(reqs, got):
        want = _reference(cfg, eng.params, r)
        np.testing.assert_allclose(rows, want, rtol=0, atol=ATOL)


@pytest.mark.parametrize("dropless", [False, True])
def test_capacity_is_the_calls_tokens_when_dropless(dropless):
    from repro.models import blocks

    cap = blocks._capacity(256, 8, 40, None if dropless else 1.25)
    assert cap == (256 if dropless else 64)
