"""The paged serving engine on multi-device meshes (subprocess — the
main pytest process keeps 1 device).

Two properties per world size:
  * slot-reuse isolation — a probe request decoded after the engine has
    filled and freed every slot and every page emits tokens
    bit-identical to the same probe on a fresh engine;
  * paged == dense-cache decode — the chunked-prefill + paged-decode
    path agrees on greedy tokens with a loop over the dense-cache decode
    program (``build_decode_step``) on the same mesh, which feeds the
    probe one token at a time and then decodes greedily.

World 4 additionally splits the overlap policy per phase (prefill
bidir/graph, decode one_shot/graph) to exercise the two-program policy
resolution under dp=2, tp=2, fsdp.
"""
import textwrap

import pytest

from conftest import run_devices

SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import ARCHS, reduced
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.ops.policy import OverlapPolicy
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import build_paged_engine
    from repro.launch.steps import build_decode_step
    from repro.serve import Request, ServeConfig

    DP, TP, SPLIT = {dp}, {tp}, {split}
    cfg = reduced(ARCHS["granite-3-2b"])
    pcfg = ParallelConfig(dp=DP, tp=TP, fsdp=True,
                          param_dtype="float32", compute_dtype="float32")
    mesh = make_mesh(DP, TP)
    scfg = ServeConfig(batch=4, max_len=32, page_size=8, chunk=8,
                       token_budget=32)
    PROBE = [11, 7, 23, 4, 19, 3]

    def probe(engine):
        r = Request(prompt=list(PROBE), max_new_tokens=5)
        engine.add(r)
        assert engine.run(max_steps=500) == []
        return list(r.out_tokens)

    def churn(engine):
        for i in range(5):   # 5 requests on 4 slots -> forced slot reuse
            engine.add(Request(prompt=[9, 8, 7, 6, 5, (i % 3) + 1],
                               max_new_tokens=4))
        assert engine.run(max_steps=500) == []

    ppol = None
    if SPLIT:  # per-phase overlap: prefill bidir, decode one_shot
        ppol = OverlapPolicy(mode="bidir", backend="graph")
        pcfg = dataclasses.replace(
            pcfg, overlap=OverlapPolicy(mode="one_shot", backend="graph"))

    paged = build_paged_engine(cfg, pcfg, scfg, mesh, prefill_policy=ppol)
    a = probe(paged)           # fresh pools
    churn(paged)               # fill + free every slot and its pages
    b = probe(paged)           # probe rides reused slot + reused pages
    assert a == b, ("paged slot reuse leaked", a, b)
    assert len(a) == 5

    # the dense-cache reference: the probe on every lane, fed one token
    # a step, then 5 greedy tokens (the paged engine's params: seed 0)
    dense = build_decode_step(
        cfg, pcfg, ShapeConfig("serve", seq_len=scfg.max_len,
                               global_batch=scfg.batch, kind="decode"),
        mesh, cache_dtype=jnp.float32)
    params, _ = dense.model.init(jax.random.PRNGKey(0), jnp.float32)
    caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          dense.in_shapes[1])
    feed, c = list(PROBE), []
    for pos in range(len(PROBE) + 4):
        tok = feed[pos] if pos < len(PROBE) else c[-1]
        logits, caches = dense.fn(params, caches, jnp.int32(pos),
                                  jnp.full((scfg.batch, 1), tok, jnp.int32))
        if pos >= len(PROBE) - 1:
            c.append(int(np.argmax(np.asarray(logits)[0])))

    assert a == c, ("paged != dense-cache decode", a, c)
    print("OK", a)
""")


@pytest.mark.parametrize(
    "devices,dp,tp,split",
    [(2, 1, 2, False), (4, 2, 2, True), (8, 4, 2, False)],
    ids=["world2-tp2", "world4-dp2tp2-phase-split", "world8-dp4tp2"],
)
def test_slot_reuse_and_paged_parity(devices, dp, tp, split):
    out = run_devices(SCRIPT.format(dp=dp, tp=tp, split=split),
                      devices=devices, timeout=1200)
    assert "OK" in out
