"""What decides ``correct``, at tiny sizes on the CPU: the served path
agrees with the reference, the lower-precision control fails the
comparison, and a run whose timed path is broken comes out not correct.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import driver, harness, model, traffic
from bench.reference import compare
from bench.reference import model as ref
from bench.tests.tiny import LIMIT, MIXES, tiny_spec


def _serve(spec, loop, seed, tmp_path, fault=None):
    mix = traffic.Mix(loop, MIXES[loop])
    return harness.serve(spec, mix, seed, 2.0, False, jax.devices(),
                         time.perf_counter(), harness.Paths(scratch=tmp_path),
                         fault)


def test_weights_made_in_one_call_unpack_to_what_was_made():
    spec = tiny_spec("moe")
    params = model.make_params(5, spec)
    key = jax.random.split(model.seed_key(5))[1]
    again = model.layer_weights(jax.random.fold_in(key, 1), spec)
    got = model.unpack_layer(params["layers"], 1, spec)
    assert sorted(got) == sorted(again)
    for k in got:
        assert jnp.array_equal(got[k], again[k]), k


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_prefill_then_paged_decode_match_the_reference(tiny_program,
                                                       tmp_path, family):
    """Every logit row the two programs produced for a finished request
    (the last prefill chunk's, then each decode step's through the
    paged cache) against the reference's full forward pass."""
    spec = tiny_spec(family)
    rows = {}  # id(request) -> {served index: program logits}

    def observe(eng):
        def wrap(kind, fn):
            def call(*a):
                logits, pools = fn(*a)
                got = np.asarray(logits, np.float32)
                if kind == "decode":
                    for i in np.flatnonzero(np.asarray(a[4])):
                        req = eng.sched.slots[i].req
                        rows.setdefault(id(req), {})[
                            len(req.out_tokens)] = got[i]
                else:
                    start, n = int(np.asarray(a[3])[0]), int(np.asarray(a[4])[0])
                    for s in eng.sched.slots:
                        if (s.phase == "prefill" and s.prompt_done == start
                                and start + n == s.prompt_len):
                            rows.setdefault(id(s.req), {})[0] = got[0]
                return logits, pools
            return call

        eng.prefill_fn = wrap("prefill", eng.prefill_fn)
        eng.decode_fn = wrap("decode", eng.decode_fn)

    got = _serve(spec, "open", 7, tmp_path, observe)
    params = model.make_params(7, spec)
    top = model.unpack_top(params, spec)
    done = [r.req for r in got.records if r.req.done][:4]
    assert done
    errs = []
    for req in done:
        tokens, positions, served = compare.positions(req)
        want = ref.logits(tokens, positions, spec,
                          lambda i: model.unpack_layer(params["layers"], i,
                                                       spec), top)
        want = np.asarray(want)
        have = np.stack([rows[id(req)][j] for j in range(len(served))])
        errs += list(np.abs(have - want).max(-1) / np.abs(want).max())
        # greedy: each served token is the program's own best
        assert (have.argmax(-1) == served).all()
    # bf16 weights and activations against float32 (8 mantissa bits); in
    # the MoE a near-tie of two experts' router scores can go either way
    # in bf16 and move a row by far more (one row of 0.30, seed 7), so
    # there the tail is not held
    assert np.median(errs) < 0.02
    assert (max(errs) if family == "dense" else np.percentile(errs, 90)) < 0.05


def _served_to_the_end(spec, seed):
    """Requests of the closed mix served to completion through the
    engine's timed path, without a clock: the same tokens every time."""
    from repro.serve import Request

    eng, programs = model.build_engine(spec, seed)
    harness.compile_programs(eng, programs, None)
    mix = traffic.Mix("closed", MIXES["closed"])
    rng = np.random.default_rng([seed, 0x7261])
    first = traffic.requests(mix, mix.params["clients"], rng,
                             spec["vocab_size"])
    reqs = [Request(prompt=r.prompt, max_new_tokens=r.max_new) for r in first]
    for r in reqs:
        eng.add(r)
    assert not eng.run()
    return [driver.Record(r, 0.0, True) for r in reqs]


def test_the_float8_control_fails_where_the_program_passes(tiny_program):
    readings = []
    for family in ("dense", "moe"):
        spec = tiny_spec(family)
        for seed in (1, 2, 3):
            records = _served_to_the_end(spec, seed)
            c = harness.check_outputs(records, spec, seed,
                                      {"logit_gap": LIMIT}, control=True)
            readings.append((c["logit_gap"]["value"],
                             c["control.logit_gap"]["value"]))
    assert max(p for p, _ in readings) <= LIMIT, readings
    assert min(c for _, c in readings) > LIMIT, readings


def _state_unchanged(eng):
    """The decode step hands back the pools it was given."""
    fn = eng.decode_fn

    def call(*a):
        before = jax.tree.map(jnp.copy, a[1])
        logits, _ = fn(*a)
        return logits, before
    eng.decode_fn = call


def _half_the_batch(eng):
    """The decode step computes half of the slots; the other half get
    the mean of the computed rows."""
    fn = eng.decode_fn

    def call(*a):
        logits, pools = fn(*a)
        half = logits.shape[0] // 2
        mean = jnp.mean(logits[:half], axis=0, keepdims=True)
        return logits.at[half:].set(jnp.broadcast_to(
            mean, logits[half:].shape)), pools
    eng.decode_fn = call


def _token_altered(eng):
    """Every 5th decode step, the best logit of each slot moves to the
    next token id."""
    fn = eng.decode_fn
    n = [0]

    def call(*a):
        logits, pools = fn(*a)
        n[0] += 1
        if n[0] % 5 == 0:
            logits = jnp.roll(logits, 1, axis=-1)
        return logits, pools
    eng.decode_fn = call


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch", "token_altered"])
@pytest.mark.parametrize("cell", ["tiny-dense.open", "tiny-moe.closed"])
def test_a_broken_timed_path_is_not_correct(tiny_program, bench_dir, capsys,
                                            fault, cell):
    code = harness.run(["--workload", cell, "--seed", "11", "--seconds",
                        "1.5", "--trace", "0"], paths=bench_dir,
                       require_tpu=False, fault=fault)
    out, err = capsys.readouterr()
    assert code == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is False, result["checks"]
