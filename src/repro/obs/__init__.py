"""repro.obs — marks for the profiler, and per-PE overlap timelines for
the shmem engine.

Two marks serve every path:

* :func:`scope` (``jax.named_scope``) names the device operations traced
  inside it. It acts at trace time: the name lands in each instruction's
  ``op_name`` metadata (``jit(f)/layers/while/body/closed_call/attn/
  paged_gather/gather``) and costs nothing at run time.
* :func:`span` (``jax.profiler.TraceAnnotation``) is a host span on the
  profiler's clock; its integer counters become the event's stats in the
  ``.xplane.pb``. With no profiler running it costs about a microsecond.

The paged serving path carries these (``serve/engine.py``
``PagedEngine.step``; ``models/lm.py``, ``models/blocks.py``). Host spans
nest as listed; scopes sit inside the two programs ``jit_paged_decode_step``
and ``jit_prefill_chunk_step`` (``launch/steps.py``):

==========================  =====  ==========================================
name                        kind   meaning (counters)
==========================  =====  ==========================================
``serve.step``              span   one working engine step (``queue``: queue
                                   depth after admission; ``slots``: slots
                                   holding a request; ``pages``: pages held)
``serve.schedule``          span   admission and planning
``serve.prefill``           span   the step's chunked-prefill call
                                   (``tokens``: valid prompt tokens of the
                                   chunk; ``streams``: streams with a chunk)
``serve.decode``            span   the step's decode call (``slots``:
                                   decoding slots; ``context``: the sum of
                                   their cached lengths; ``pages``: the
                                   pages the decode kernel reads, the sum
                                   of ``ceil((length + 1) / page_size)``)
``serve.<phase>.launch``    span   build and upload the host arrays, dispatch
``serve.<phase>.fetch``     span   ``np.asarray(logits)``: the wait for the
                                   program and the copy back
``serve.<phase>.sample``    span   sampling and emitting the tokens
``embed``                   scope  the embedding lookup
``layers``                  scope  the ``lax.scan`` over layers; its own
                                   operations (slicing each layer's weights
                                   and pools out of the stacked leaves, the
                                   write-back) lie outside the body's
                                   ``closed_call``
``attn``                    scope  one attention layer, with children
                                   ``qkv`` (norm, projections, rope),
                                   ``kv_write`` (the pool writes),
                                   ``paged_gather`` (pages to per-slot K/V;
                                   prefill, and decode off the chip),
                                   ``flash_decode`` (decode) or
                                   ``chunk_attend`` (prefill), ``out``
                                   (output projection and its reduction)
``mlp`` / ``moe``           scope  the feed-forward block; ``moe``'s children
                                   ``router`` (norm, router logits, softmax
                                   and top-k), ``dispatch`` (the scatter
                                   into the experts' buffers, and an
                                   expert-parallel AllToAll out),
                                   ``experts`` (the grouped GEMMs and their
                                   activation), ``combine`` (the weighted
                                   gather back, the AllToAll home or the
                                   reduction over tensor-parallel ranks)
``logits``                  scope  final norm and unembedding
==========================  =====  ==========================================

The spans and scopes need no switch: ``jax.profiler.trace`` records them
and they cost nearly nothing without it. The ``enable()`` switch below
belongs to the shmem timelines alone.

The paper's claim is that compiler-generated overlapping kernels hide
communication latency. The rest of this package makes the overlap
*visible*: when tracing is enabled, every host-side op of the emulated DMA backend
(:mod:`repro.shmem.emulated`) appends a timestamped per-PE
:class:`TraceEvent` into its world's ring buffer — puts, signals,
credit/arrival waits, barriers, reads — and the tile executor brackets
its per-chunk computes with ``tile_compute`` (and wire ``pack`` /
``decode``) spans. A drained event list exports as a Chrome-trace /
Perfetto JSON (:mod:`repro.obs.trace`) and reduces to overlap-efficiency
stats (:mod:`repro.obs.metrics`):

    overlap_efficiency = 1 - exposed_comm / wall

where ``exposed_comm`` is the mean per-PE stall time (credit waits +
arrival waits + mid-stream barrier flushes — the communication and
synchronization the schedule failed to hide behind compute; only a
PE's FIRST barrier per kernel instance is launch skew, reported
separately).

Semantics
---------
* ``enable()`` / ``disable()`` flip one global flag. Host-side event
  recording is gated at RUN time (one bool check per callback — no
  measurable overhead when disabled), but the executor's compute *spans*
  are gated at TRACE time: enable tracing BEFORE the first
  jit-compilation of the program you want span-annotated (a program
  traced while disabled carries no span callbacks, and jax's jit cache
  will keep reusing it). With tracing disabled the traced program is the
  seed program — outputs are bit-identical.
* On the real-TPU pltpu backend there are no host callbacks to
  timestamp; the SAME span labels are mapped onto a :func:`scope` and a
  :func:`span` (see :func:`phase`), so a real profiler capture
  (``jax.profiler.trace``) carries identical
  ``obs.tile_compute`` / ``obs.pack`` / ``obs.decode`` labels.
* Trace buffers live per shmem world (per traced-kernel instance) and
  are bounded rings: ``enable(capacity=...)`` sets the per-world event
  cap. ``shmem.emulated.reset()`` drops the worlds and their traces —
  drain with :func:`events` first.

Quickstart (see ``examples/trace_overlap.py``)::

    from repro import obs
    obs.enable()
    y = step()                      # emulated kernel-backend run
    ev = obs.events(clear=True)
    obs.trace.save("trace.json", ev)          # open in ui.perfetto.dev
    print(obs.metrics.summarize(ev))
"""
from __future__ import annotations

import collections
import contextlib
import threading
from typing import List, NamedTuple, Optional

import jax


class TraceEvent(NamedTuple):
    """One timestamped per-PE event of the emulated shmem engine."""

    pe: int        # the PE (linearized rank) the event belongs to
    cid: int       # collective_id of the kernel instance
    kind: str      # put | signal | credit_wait | arrival_wait | barrier |
    #                read | alloc | tile_compute | pack | decode
    name: str      # symmetric buffer / signal name ("" for spans)
    bytes: int     # payload bytes (puts/reads; 0 otherwise)
    t0: float      # span begin, seconds (time.perf_counter clock)
    t1: float      # span end, seconds


# Event kinds counted as exposed communication (stall) by the metrics
# reduction: credit waits (flow control) and arrival waits (data deps).
STALL_KINDS = ("credit_wait", "arrival_wait")
# Event kinds counted as compute-busy time.
COMPUTE_KINDS = ("tile_compute",)

_lock = threading.Lock()
_enabled = False
_capacity = 65536


def enabled() -> bool:
    """Is tracing on? Checked at run time by the emulated host ops and at
    trace time by the executor's span instrumentation."""
    return _enabled


def capacity() -> int:
    """Per-world ring-buffer capacity (events)."""
    return _capacity


def enable(capacity: Optional[int] = None) -> None:
    """Turn tracing on (optionally resizing the per-world ring buffers).

    Enable BEFORE the first compilation of the program you want
    span-annotated — span instrumentation is decided at trace time.
    """
    global _enabled, _capacity
    from ..shmem import emulated as em  # lazy: avoid import cycle

    with _lock:
        if capacity is not None:
            _capacity = int(capacity)
        _enabled = True
    with em._worlds_lock:
        worlds = list(em._worlds.values())
    for w in worlds:
        with w.cond:
            if w.trace.maxlen != _capacity:
                w.trace = collections.deque(w.trace, maxlen=_capacity)


def disable() -> None:
    """Turn tracing off (recorded events stay until :func:`clear` or
    ``shmem.emulated.reset``)."""
    global _enabled
    with _lock:
        _enabled = False


@contextlib.contextmanager
def tracing(capacity: Optional[int] = None):
    """Scoped ``enable()`` / ``disable()``."""
    enable(capacity)
    try:
        yield
    finally:
        disable()


def events(clear: bool = False) -> List[TraceEvent]:
    """Drain the per-world ring buffers into one t0-sorted event list.

    ``clear=True`` empties the buffers (and any un-ended pending spans)
    after collecting — use it to attribute events to one run at a time.
    """
    from ..shmem import emulated as em

    with em._worlds_lock:
        worlds = list(em._worlds.values())
    out: List[TraceEvent] = []
    for w in worlds:
        with w.cond:
            out.extend(w.trace)
            if clear:
                w.trace.clear()
                w.pending.clear()
    out.sort(key=lambda ev: ev.t0)
    return out


def clear() -> None:
    """Empty every world's trace ring buffer."""
    events(clear=True)


def scope(name: str):
    """A device scope: ``jax.named_scope(name)``. Operations traced inside
    it carry ``name`` in their ``op_name`` metadata; nothing runs."""
    return jax.named_scope(name)


def span(name: str, **counts: int):
    """A host span on the profiler's clock:
    ``jax.profiler.TraceAnnotation(name, **counts)``. Each counter becomes
    a stat of the trace event; ``set_metadata(**counts)`` on the span adds
    counters known only after it opened."""
    return jax.profiler.TraceAnnotation(name, **counts)


@contextlib.contextmanager
def phase(kind: str, name: str = ""):
    """The backend-independent span label: ``obs.<kind>[.<name>]``, both
    as a :func:`scope` (real-TPU profiles of the pltpu protocols carry the
    same ``obs.tile_compute`` / ``obs.pack`` / ``obs.decode`` names the
    emulated timeline records) and as a host :func:`span`."""
    label = f"obs.{kind}" + (f".{name}" if name else "")
    with scope(label), span(label):
        yield


from . import metrics, trace  # noqa: E402  (need the names above)

__all__ = [
    "TraceEvent",
    "STALL_KINDS",
    "COMPUTE_KINDS",
    "enabled",
    "enable",
    "disable",
    "tracing",
    "capacity",
    "events",
    "clear",
    "phase",
    "scope",
    "span",
    "metrics",
    "trace",
]
