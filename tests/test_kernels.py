"""Pallas kernel vs. pure-jnp oracle allclose sweeps (shapes x dtypes).

Single-device: kernels run in interpret mode (pl.pallas_call on CPU)."""
import os
import sys
sys.path.insert(0, os.path.dirname(__file__))

import jax.numpy as jnp
import numpy as np
import pytest

import proptest as pt
from repro.kernels import flash_decode as fd
from repro.kernels import ops, ref

R = np.random.RandomState(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(R.randn(*shape) * scale, dtype)


# ---------------------------------------------------------------- matmul
@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (128, 256, 64), (96, 200, 130),
                                   (256, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, dtype):
    a, b = _arr((m, k), dtype), _arr((k, n), dtype)
    got = ops.matmul(a, b, force="pallas", bm=64, bk=64, bn=64)
    want = ref.matmul(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("rank,world", [(0, 4), (2, 4), (3, 4), (1, 2)])
def test_matmul_swizzled_grid(rank, world):
    a, b = _arr((256, 64)), _arr((64, 64))
    got = ops.matmul(a, b, force="pallas", bm=32, bk=64, bn=64,
                     rank=rank, world=world)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul(a, b)),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------- grouped matmul
@pytest.mark.parametrize("e,cap,k,n", [(4, 64, 96, 80), (8, 32, 64, 64),
                                       (2, 128, 48, 96)])
def test_grouped_matmul_sweep(e, cap, k, n):
    x, w = _arr((e, cap, k)), _arr((e, k, n))
    got = ops.grouped_matmul(x, w, force="pallas", bm=32, bk=32, bn=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.grouped_matmul(x, w)),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(hq, hkv, causal):
    q = _arr((2, hq, 128, 32))
    k = _arr((2, hkv, 128, 32))
    v = _arr((2, hkv, 128, 32))
    got = ops.flash_attention(q, k, v, causal=causal, force="pallas", bq=32, bkv=32)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-4)


def test_flash_attention_bf16():
    q = _arr((1, 2, 64, 32), jnp.bfloat16)
    k = _arr((1, 2, 64, 32), jnp.bfloat16)
    v = _arr((1, 2, 64, 32), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, force="pallas", bq=32, bkv=32)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_flash_attention_chunked_matches_plain():
    q, k, v = _arr((2, 4, 128, 32)), _arr((2, 2, 128, 32)), _arr((2, 2, 128, 32))
    for causal in (True, False):
        a = ref.flash_attention(q, k, v, causal=causal)
        b = ref.flash_attention_chunked(q, k, v, causal=causal, kv_chunk=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ flash decode
@pt.given(examples=8, s=pt.sampled_from([64, 128, 256]),
          hq=pt.sampled_from([2, 4]), hkv=pt.sampled_from([1, 2]))
def test_flash_decode_sweep(s, hq, hkv):
    b, d = 2, 32
    q = _arr((b, hq, d))
    k = _arr((b, hkv, s, d))
    v = _arr((b, hkv, s, d))
    lens = jnp.asarray([s, s // 2], jnp.int32)
    og, lg = ops.flash_decode(q, k, v, lens, force="pallas", bkv=32)
    ow, lw = ref.flash_decode(q, k, v, length=lens)
    np.testing.assert_allclose(np.asarray(og), np.asarray(ow), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lw), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s,bkv,lens", [(80, 32, (80, 33)), (48, 32, (1, 17)),
                                        (96, 32, (5, 96))])
def test_flash_decode_ragged_tiles(s, bkv, lens):
    """A cache that is not a multiple of the KV tile (page geometries
    that do not divide it), and slots whose length ends tiles early."""
    b, hq, hkv, d = 2, 4, 2, 32
    q = _arr((b, hq, d))
    k = _arr((b, hkv, s, d))
    v = _arr((b, hkv, s, d))
    lens = jnp.asarray(lens, jnp.int32)
    og, lg = ops.flash_decode(q, k, v, lens, force="pallas", bkv=bkv)
    ow, lw = ref.flash_decode(q, k, v, length=lens)
    np.testing.assert_allclose(np.asarray(og), np.asarray(ow), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lw), atol=2e-5, rtol=2e-5)


def test_flash_decode_empty_shard_drops_out_of_combine():
    """Sequence-parallel shards: a slot whose sequence ends before the
    second shard gives that shard length 0. Its partial must weigh
    nothing in the combine, so the merged output equals the whole-cache
    reference."""
    b, hq, hkv, s, d = 2, 4, 2, 64, 32
    q = _arr((b, hq, d))
    k = _arr((b, hkv, s, d))
    v = _arr((b, hkv, s, d))
    lens = np.asarray([20, 50], np.int32)
    parts = [ops.flash_decode(q, k[:, :, lo:lo + 32], v[:, :, lo:lo + 32],
                              jnp.asarray(np.clip(lens - lo, 0, 32)),
                              force="pallas", bkv=16)
             for lo in (0, 32)]
    got = ops.combine_flash_decode(jnp.stack([o for o, _ in parts]),
                                   jnp.stack([lse for _, lse in parts]))
    want, _ = ref.flash_decode(q, k, v, length=jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-4)


# ------------------------------------------------------ paged flash decode
PAGE = 16


def _paged_case(hq, hkv, d, dtype, garbage):
    """A pool of scattered pages (page 0 the scratch page), one slot per
    length: 1, 15, 16, 17 tokens, a ragged 45 and a full table. Returns
    (q, table, lengths, clean pools, dirty pools): the dirty pools hold
    ``garbage`` on page 0 and past each slot's length in its last page,
    the clean ones zeros there."""
    pages_per_slot = 6
    lens = np.asarray([1, 15, 16, 17, 45, pages_per_slot * PAGE], np.int32)
    b = len(lens)
    live = -(-lens // PAGE)
    n_pages = 1 + int(live.sum()) + 5
    ids = 1 + R.permutation(n_pages - 1)  # pages in no order, never page 0
    table = np.zeros((b, pages_per_slot), np.int32)  # unused entries: page 0
    dead = np.zeros((n_pages, PAGE), bool)
    dead[0] = True
    at = 0
    for i, n in enumerate(live):
        table[i, :n] = ids[at:at + n]
        at += n
        dead[table[i, n - 1], lens[i] - (n - 1) * PAGE:] = True
    q = _arr((b, hq, d))
    pools = [R.randn(n_pages, hkv, PAGE, d).astype(np.float32)
             for _ in range(2)]
    mask = dead[:, None, :, None]
    stored = (n_pages, hkv, *fd.page_rows(PAGE, d))  # the tokens in order
    clean = [jnp.asarray(np.where(mask, 0.0, p).reshape(stored), dtype)
             for p in pools]
    dirty = [jnp.asarray(np.where(mask, garbage, p).reshape(stored), dtype)
             for p in pools]
    return q, jnp.asarray(table), jnp.asarray(lens), clean, dirty


@pytest.mark.parametrize("garbage", [np.nan, 3e4], ids=["nan", "large"])
@pytest.mark.parametrize("hq,hkv,d,dtype", [
    (32, 8, 64, jnp.bfloat16), (32, 8, 128, jnp.float32),
    (8, 2, 64, jnp.float32), (8, 2, 128, jnp.bfloat16)],
    ids=["32-8x64-bf16", "32-8x128", "8-2x64", "8-2x128-bf16"])
def test_paged_flash_decode_matches_gathered_reference(hq, hkv, d, dtype,
                                                       garbage):
    """The paged kernel, reading pages in place through the table, against
    the whole-table gather plus ref.flash_decode. Whatever lies past a
    slot's length (scratch page 0, the rest of its last page) must not
    reach o or lse, so the reference reads zeros there and the kernel
    garbage. Blocks of two pages: slots span several blocks, and a
    slot's last block prefetches the next slot's first."""
    q, table, lens, (ck, cv), (dk, dv) = _paged_case(hq, hkv, d, dtype,
                                                     garbage)
    og, lg = ops.paged_flash_decode(q, dk[None], dv[None], table, lens, 0,
                                    bkv=2 * PAGE, force="pallas")
    ow, lw = ops.paged_flash_decode(q, ck[None], cv[None], table, lens, 0,
                                    force="ref")
    np.testing.assert_allclose(np.asarray(og), np.asarray(ow), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lw), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("hq,hkv,d,dtype", [
    (32, 8, 64, jnp.bfloat16), (8, 2, 128, jnp.float32)],
    ids=["32-8x64-bf16", "8-2x128"])
def test_paged_flash_decode_reads_one_layer_of_stacked_pools(hq, hkv, d,
                                                             dtype, layer):
    """Pools stacked over three layers, each layer's pages different, read
    at ``layer``: the kernel's output is the gathered reference of that
    layer's pool alone. Table entries past a slot's live pages point at
    random pages, which hold other slots' tokens; neither they nor the
    other layers reach o or lse."""
    q, table, lens, clean, dirty = _paged_case(hq, hkv, d, dtype, np.nan)
    scales = (1.0, -0.5, 2.0)  # exact in bf16: three different layers
    live = -(-np.asarray(lens) // PAGE)
    anywhere = R.randint(0, clean[0].shape[0], table.shape)
    table = jnp.asarray(np.where(np.arange(table.shape[1]) < live[:, None],
                                 table, anywhere), jnp.int32)
    stacked = [jnp.stack([pool * s for s in scales]) for pool in dirty]
    og, lg = ops.paged_flash_decode(q, *stacked, table, lens,
                                    jnp.int32(layer), bkv=2 * PAGE,
                                    force="pallas")
    ck, cv = (pool[None] * scales[layer] for pool in clean)
    ow, lw = ops.paged_flash_decode(q, ck, cv, table, lens, 0, force="ref")
    np.testing.assert_allclose(np.asarray(og), np.asarray(ow), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lw), atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------- ssd scan
@pt.given(examples=6, l=pt.sampled_from([32, 64]), h=pt.sampled_from([2, 4]),
          g=pt.sampled_from([1, 2]), chunk=pt.sampled_from([8, 16, 32]))
def test_ssd_scan_sweep(l, h, g, chunk):
    if h % g != 0:
        g = 1
    b, p, s = 2, 16, 16
    x = _arr((b, l, h, p), scale=0.5)
    dt = jnp.asarray(R.rand(b, l, h) * 0.5 + 0.01, jnp.float32)
    a = jnp.asarray(-np.abs(R.rand(h)) - 0.1, jnp.float32)
    bm = _arr((b, l, g, s), scale=0.3)
    cm = _arr((b, l, g, s), scale=0.3)
    yg, sg = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, force="pallas")
    yw, sw = ref.ssd_scan(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yw), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sg), np.asarray(sw), atol=1e-4, rtol=1e-4)


@pt.given(examples=6, l=pt.sampled_from([32, 64]), chunk=pt.sampled_from([8, 16]))
def test_ssd_chunked_matches_sequential(l, chunk):
    """The chunked closed form (production XLA path) == per-step scan."""
    b, h, p, g, s = 2, 4, 16, 2, 16
    x = _arr((b, l, h, p), scale=0.5)
    dt = jnp.asarray(R.rand(b, l, h) * 0.5 + 0.01, jnp.float32)
    a = jnp.asarray(-np.abs(R.rand(h)) - 0.1, jnp.float32)
    bm = _arr((b, l, g, s), scale=0.3)
    cm = _arr((b, l, g, s), scale=0.3)
    y1, s1 = ref.ssd_scan(x, dt, a, bm, cm)
    y2, s2 = ref.ssd_scan_chunked(x, dt, a, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4, rtol=1e-4)


def test_ssd_scan_state_continuity():
    """Scanning two halves with carried state == scanning the whole."""
    b, l, h, p, g, s = 1, 64, 2, 16, 1, 16
    x = _arr((b, l, h, p), scale=0.5)
    dt = jnp.asarray(R.rand(b, l, h) * 0.3 + 0.01, jnp.float32)
    a = jnp.asarray(-np.abs(R.rand(h)) - 0.1, jnp.float32)
    bm = _arr((b, l, g, s), scale=0.3)
    cm = _arr((b, l, g, s), scale=0.3)
    y_full, s_full = ref.ssd_scan(x, dt, a, bm, cm)
    y1, s1 = ref.ssd_scan(x[:, :32], dt[:, :32], a, bm[:, :32], cm[:, :32])
    y2, s2 = ref.ssd_scan(x[:, 32:], dt[:, 32:], a, bm[:, 32:], cm[:, 32:],
                          init_state=s1)
    np.testing.assert_allclose(np.asarray(y_full[:, 32:]), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2), atol=1e-4, rtol=1e-4)


# ------------------------------------------------- decode combine property
@pt.given(examples=10, w=pt.sampled_from([2, 4, 8, 16]))
def test_combine_flash_decode_partition_invariance(w):
    """Splitting KV into W shards and combining == direct attention."""
    b, h, s, d = 2, 2, 64, 16
    q = _arr((b, h, d))
    k = _arr((b, h, s, d))
    v = _arr((b, h, s, d))
    full_o, _ = ref.flash_decode(q, k, v)
    assert s % w == 0
    chunk = s // w
    os_, ls_ = [], []
    for i in range(w):
        o, l = ref.flash_decode(q, k[:, :, i * chunk:(i + 1) * chunk],
                                v[:, :, i * chunk:(i + 1) * chunk])
        os_.append(o)
        ls_.append(l)
    got = ref.combine_flash_decode(jnp.stack(os_), jnp.stack(ls_))
    np.testing.assert_allclose(np.asarray(got), np.asarray(full_o), atol=1e-5, rtol=1e-4)
