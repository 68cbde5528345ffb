"""The pltpu backend: shmem primitives INSIDE a Pallas TPU kernel.

The faithful port of the paper's OpenSHMEM / NVSHMEM primitive set to
TPU hardware. Symmetric memory is ``pl.ANY`` refs under SPMD shard_map
(declare workspaces as extra kernel outputs so the interpreter and
Mosaic both give them stable cross-device addresses); signals are
DMA/REGULAR semaphores; data transfer is the chip's async remote-DMA
engine. The recv semaphore *is* the paper's signal: TPU DMAs signal
data arrival in hardware, which is why the LL flag-in-word protocol
does not need porting.

These functions are only meaningful inside a Pallas kernel body and
only lower on real TPU (Mosaic). For the CPU-emulated implementation of
the same API (value-level, host-side symmetric heaps) see
:mod:`repro.shmem.emulated`.
"""
from __future__ import annotations

from typing import Optional

from jax import lax
from jax.experimental.pallas import tpu as pltpu


def annotate(kind: str, name: str = ""):
    """The pltpu mapping of :mod:`repro.obs` span labels: a
    ``jax.named_scope`` (+ profiler TraceAnnotation) context, so a real
    TPU profile of a pallas protocol carries the SAME
    ``obs.tile_compute`` / ``obs.pack`` / ``obs.decode`` labels the
    emulated backend's host timeline records. Trace-time metadata only —
    zero runtime cost."""
    from .. import obs

    return obs.phase(kind, name)


def _device_id(peer):
    """MESH device id: scalar peer = 1D mesh; tuple peer = one coordinate
    per mesh axis (the two-level protocols address a (pod, ring) grid —
    the kernel's mesh axis order must match the tuple order)."""
    return tuple(peer) if isinstance(peer, tuple) else (peer,)


def putmem_signal_nbi(
    src_ref,
    dst_ref,
    send_sem,
    recv_sem,
    peer,
    *,
    axis: Optional[str] = None,
):
    """Non-blocking one-sided put + arrival signal (paper: putmem_signal_nbi).

    Starts an async remote DMA copying ``src_ref`` (local) into ``dst_ref``
    *on device* ``peer`` along mesh axis ``axis``. The remote ``recv_sem``
    is incremented by the hardware when the data lands — the signal write
    and the data transfer are one operation, as in NVSHMEM's putmem_signal.
    Returns the copy descriptor; call ``.wait()`` (or ``quiet``) later.
    """
    del axis
    copy = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=_device_id(peer),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    copy.start()
    return copy


def putmem_signal(src_ref, dst_ref, send_sem, recv_sem, peer, *, axis=None):
    """Blocking variant: returns after the local send side has completed."""
    copy = putmem_signal_nbi(src_ref, dst_ref, send_sem, recv_sem, peer, axis=axis)
    copy.wait_send()
    return copy


def local_copy_nbi(src_ref, dst_ref, sem):
    """Async local (HBM<->HBM/VMEM) DMA — the 'copy engine' analogue."""
    copy = pltpu.make_async_copy(src_ref, dst_ref, sem)
    copy.start()
    return copy


def signal_op(sem, peer, *, inc: int = 1, axis: Optional[str] = None):
    """Increment a remote signal (paper: signal_op / notify)."""
    del axis
    pltpu.semaphore_signal(
        sem,
        inc=inc,
        device_id=_device_id(peer),
        device_id_type=pltpu.DeviceIdType.MESH,
    )


notify = signal_op


def signal_wait_until(sem, value: int):
    """Spin-wait until the local signal reaches ``value``, then consume it
    (paper: signal_wait_until / wait)."""
    pltpu.semaphore_wait(sem, value)


wait = signal_wait_until


def quiet(*copies):
    """Ensure completion of outstanding one-sided ops (paper: quiet)."""
    for c in copies:
        c.wait()


def barrier_all(axis: str, world: int):
    """Barrier across all ranks on ``axis`` (paper: barrier_all).

    Uses the kernel's collective barrier semaphore: signal every peer, then
    wait for ``world - 1`` arrivals. Requires
    ``compiler_params=pltpu.CompilerParams(collective_id=...)``.
    """
    barrier = pltpu.get_barrier_semaphore()
    me = lax.axis_index(axis)
    for off in range(1, world):
        peer = lax.rem(me + off, world)
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=(peer,), device_id_type=pltpu.DeviceIdType.MESH
        )
    pltpu.semaphore_wait(barrier, world - 1)


def barrier_all_grid(axes, worlds):
    """Barrier across a two-axis (outer, inner) device grid (the
    two-level protocols' rendezvous): signal every (o, i) peer on the
    kernel's collective barrier semaphore, wait for Wo*Wi - 1 arrivals.
    ``axes``/``worlds`` are ordered (outer, inner), matching the 2D
    device ids the protocols use."""
    outer, inner = axes
    wo, wi = worlds
    barrier = pltpu.get_barrier_semaphore()
    oid = lax.axis_index(outer)
    iid = lax.axis_index(inner)
    for o_off in range(wo):
        for i_off in range(wi):
            if o_off == 0 and i_off == 0:
                continue  # self
            peer = (lax.rem(oid + o_off, wo), lax.rem(iid + i_off, wi))
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=peer,
                device_id_type=pltpu.DeviceIdType.MESH,
            )
    pltpu.semaphore_wait(barrier, wo * wi - 1)


def broadcast_put(src_ref, dst_ref, send_sem, recv_sem, axis: str, world: int):
    """multimem_st analogue: store the same data to all peers.

    ICI exposes no multicast primitive, so this is a peer loop of one-sided
    puts (documented hardware-adaptation change). All DMAs are started
    before any wait — they proceed in parallel on the DMA engines.
    """
    me = lax.axis_index(axis)
    copies = []
    for off in range(1, world):
        peer = lax.rem(me + off, world)
        copies.append(
            putmem_signal_nbi(src_ref, dst_ref, send_sem, recv_sem, peer, axis=axis)
        )
    for c in copies:
        c.wait_send()
