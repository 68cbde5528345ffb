"""MoE dispatch/combine properties (single device)."""
import os
import sys
sys.path.insert(0, os.path.dirname(__file__))

import jax.numpy as jnp
import numpy as np

import proptest as pt
from repro.core import moe_overlap as mo

R = np.random.RandomState(0)


@pt.given(examples=12, t=pt.sampled_from([8, 16, 32]), e=pt.sampled_from([4, 8]),
          k=pt.sampled_from([1, 2, 4]))
def test_dispatch_combine_identity(t, e, k):
    """With no drops, combine(identity_expert(dispatch(x))) == x because
    the top-k weights renormalize to 1."""
    d = 16
    x = jnp.asarray(R.randn(t, d), jnp.float32)
    logits = jnp.asarray(R.randn(t, e), jnp.float32)
    disp, info = mo.dispatch(x, *mo.topk_route(logits, k), e, t * k)
    y = mo.topk_combine(disp, info)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5, rtol=1e-5)


@pt.given(examples=12, t=pt.sampled_from([16, 32]), e=pt.sampled_from([4, 8]))
def test_dispatch_respects_capacity(t, e):
    d, k, cap = 8, 2, 8
    x = jnp.asarray(R.randn(t, d), jnp.float32)
    logits = jnp.asarray(R.randn(t, e), jnp.float32)
    disp, info = mo.dispatch(x, *mo.topk_route(logits, k), e, cap)
    assert disp.shape == (e, cap, d)
    assert bool(jnp.all(info.position < cap))
    # weights of kept slots are positive, dropped slots zero, all finite
    assert bool(jnp.all(jnp.isfinite(info.weight)))
    assert bool(jnp.all(info.weight >= 0))


@pt.given(examples=10, t=pt.sampled_from([16, 32]), e=pt.sampled_from([4, 8]),
          k=pt.sampled_from([1, 2]))
def test_dispatch_slot_uniqueness(t, e, k):
    """No two kept token-slots map to the same (expert, position)."""
    d = 4
    x = jnp.asarray(R.randn(t, d), jnp.float32)
    logits = jnp.asarray(R.randn(t, e), jnp.float32)
    cap = t * k
    disp, info = mo.dispatch(x, *mo.topk_route(logits, k), e, cap)
    kept = np.asarray(info.weight).reshape(-1) > 0
    pairs = np.stack([np.asarray(info.expert).reshape(-1),
                      np.asarray(info.position).reshape(-1)], 1)[kept]
    assert len({tuple(p) for p in pairs}) == kept.sum()


def test_combine_weights_sum_to_one():
    t, e, k, d = 32, 8, 4, 8
    x = jnp.asarray(R.randn(t, d), jnp.float32)
    logits = jnp.asarray(R.randn(t, e), jnp.float32)
    _, info = mo.dispatch(x, *mo.topk_route(logits, k), e, t * k)
    np.testing.assert_allclose(np.asarray(info.weight.sum(-1)), 1.0, atol=1e-5)
