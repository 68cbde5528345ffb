"""Public kernel entry points.

Dispatch policy (production): compiled Pallas on TPU, always — neither
interpret mode nor ref.py is ever picked there on its own. Elsewhere the
default is pure jnp (ref.py math, XLA-fused) so that graph-level
compilation (dry-run, smoke tests) sees ordinary HLO, and
``force="pallas"`` runs the kernels in interpret mode for the
kernel-vs-ref test sweeps. ``force="ref"`` pins the jnp math anywhere.
"""
from __future__ import annotations

from typing import Literal, Optional

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import flash_decode as _fd
from . import grouped_matmul as _gmm
from . import matmul as _mm
from . import ref as _ref
from . import ssd_scan as _ssd

Force = Optional[Literal["pallas", "ref"]]


def _platform() -> str:
    """The platform the kernels are built for (a test that compiles for
    a described chip on a CPU host steers this)."""
    return jax.default_backend()


def use_pallas(force: Force = None) -> bool:
    """Whether the kernels run as Pallas here (compiled on a TPU,
    interpreted elsewhere) rather than as ref.py's jnp math."""
    if force is not None:
        return force == "pallas"
    return _platform() == "tpu"


def _interpret() -> bool:
    return _platform() != "tpu"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def matmul(a, b, *, out_dtype=jnp.float32, bm=256, bk=512, bn=256,
           rank=0, world=1, force: Force = None):
    if not use_pallas(force):
        return _ref.matmul(a, b, out_dtype)
    m, k = a.shape
    _, n = b.shape
    bm_, bk_, bn_ = min(bm, m), min(bk, k), min(bn, n)
    ap = _pad_to(_pad_to(a, bm_, 0), bk_, 1)
    bp = _pad_to(_pad_to(b, bk_, 0), bn_, 1)
    out = _mm.matmul(ap, bp, bm=bm_, bk=bk_, bn=bn_, out_dtype=out_dtype,
                     rank=rank, world=world, interpret=_interpret())
    return out[:m, :n]


def grouped_matmul(x, w, *, out_dtype=jnp.float32, bm=128, bk=512, bn=256,
                   force: Force = None):
    if not use_pallas(force):
        return _ref.grouped_matmul(x, w, out_dtype)
    e, cap, k = x.shape
    _, _, n = w.shape
    bm_, bk_, bn_ = min(bm, cap), min(bk, k), min(bn, n)
    xp = _pad_to(_pad_to(x, bm_, 1), bk_, 2)
    wp = _pad_to(_pad_to(w, bk_, 1), bn_, 2)
    out = _gmm.grouped_matmul(xp, wp, bm=bm_, bk=bk_, bn=bn_,
                              out_dtype=out_dtype, interpret=_interpret())
    return out[:, :cap, :n]


def flash_attention(q, k, v, *, causal=True, scale=None, bq=256, bkv=256,
                    force: Force = None):
    if not use_pallas(force):
        if k.shape[2] > 1024:
            # long sequences: chunked online softmax (O(Lq*chunk) memory)
            return _ref.flash_attention_chunked(q, k, v, causal=causal, scale=scale)
        return _ref.flash_attention(q, k, v, causal=causal, scale=scale)
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               bq=bq, bkv=bkv, interpret=_interpret())


def flash_decode(q, k, v, length, *, scale=None, bkv=512, force: Force = None):
    if not use_pallas(force):
        return _ref.flash_decode(q, k, v, scale=scale, length=length)
    return _fd.flash_decode(q, k, v, length, scale=scale, bkv=bkv,
                            interpret=_interpret())


def paged_flash_decode(q, pool_k, pool_v, table, length, layer, *,
                       scale=None, bkv=512, force: Force = None):
    """Flash decode through per-slot block tables over one layer of page
    pools stacked over layers."""
    if not use_pallas(force):
        return _ref.paged_flash_decode(q, pool_k, pool_v, table, length,
                                       layer, scale=scale)
    return _fd.paged_flash_decode(q, pool_k, pool_v, table, length, layer,
                                  scale=scale, bkv=bkv,
                                  interpret=_interpret())


def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk=128, force: Force = None):
    if not use_pallas(force):
        # chunked closed form: O(L/chunk)-deep scan (the per-timestep
        # reference would save a state residual per step in backward)
        return _ref.ssd_scan_chunked(x, dt, a, b_mat, c_mat, chunk=chunk)
    return _ssd.ssd_scan(x, dt, a, b_mat, c_mat, chunk=chunk,
                         interpret=_interpret())


combine_flash_decode = _ref.combine_flash_decode
