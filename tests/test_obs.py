"""repro.obs: trace-event schema, metrics reduction, stall watchdog,
reset-in-flight guard, and disabled-mode bit-identity / overhead."""
import threading
import time

import numpy as np
import pytest

from conftest import run_devices


# ---------------------------------------------------------------------------
# Trace-event schema on a real 4-PE ring_ag run (subprocess: needs devices)
# ---------------------------------------------------------------------------


_RING_TRACE_SCRIPT = r"""
import functools, json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro import obs
from repro.core.collective_matmul import make_sharded
from repro.ops import ag_matmul

W = jax.device_count()
assert W == 4, W
mesh = jax.make_mesh((W,), ("tp",))
M, K, N = 8 * W, 16, 4 * W
x = jax.random.normal(jax.random.key(0), (M, K), jnp.float32)
w = jax.random.normal(jax.random.key(1), (K, N), jnp.float32)
specs = ((P("tp", None), P(None, "tp")), P(None, "tp"))

def build():
    return make_sharded(
        functools.partial(ag_matmul, axis="tp", mode="ring",
                          backend="kernel", out_dtype=jnp.float32),
        mesh, *specs)

# run 1: tracing disabled (reference output)
y_ref = np.asarray(build()(x, w))
assert not obs.events(), "no events may be recorded while disabled"

# run 2: tracing enabled (fresh build -> fresh trace with spans)
obs.enable()
y_traced = np.asarray(build()(x, w))
events = obs.events(clear=True)
obs.disable()

# run 3: disabled again — bit-identity with run 1
y_after = np.asarray(build()(x, w))
assert (y_ref == y_traced).all(), "tracing perturbed the result"
assert (y_ref == y_after).all(), "disable() did not restore the seed program"

# schema: every event field well-formed
per_pe = {}
for ev in events:
    assert 0 <= ev.pe < W, ev
    assert ev.t1 >= ev.t0 >= 0.0, ev
    assert ev.bytes >= 0, ev
    per_pe.setdefault(ev.pe, []).append(ev)
assert sorted(per_pe) == list(range(W)), sorted(per_pe)

counts = {}
for ev in events:
    counts[ev.kind] = counts.get(ev.kind, 0) + 1
# ring protocol, per PE: W-1 puts, W-1 credit waits, W-1 arrival waits,
# W tile computes, 2 barriers
assert counts["put"] == W * (W - 1), counts
assert counts["credit_wait"] == W * (W - 1), counts
assert counts["arrival_wait"] == W * (W - 1), counts
assert counts["tile_compute"] == W * W, counts
assert counts["barrier"] == 2 * W, counts
# every put has a matching arrival wait on the receiving side
assert counts["put"] == counts["arrival_wait"], counts
# wire bytes: each put ships one (M/W, K) f32 chunk
chunk_bytes = (M // W) * K * 4
put_bytes = sum(ev.bytes for ev in events if ev.kind == "put")
assert put_bytes == W * (W - 1) * chunk_bytes, (put_bytes, chunk_bytes)

s = obs.metrics.summarize(events, op="ag_matmul", mode="ring",
                          backend="kernel")
assert 0.0 < s.overlap_efficiency <= 1.0, s
assert s.n_pes == W and s.wire_bytes == put_bytes, s
assert s.labels["op"] == "ag_matmul", s.labels

# chrome-trace export round-trips
doc = obs.trace.chrome_trace(events)
xev = [r for r in doc["traceEvents"] if r.get("ph") == "X"]
assert len(xev) == len(events), (len(xev), len(events))
assert {r["tid"] for r in xev} == set(range(W))
json.dumps(doc)  # serializable
print("RING_TRACE_OK")
"""


def test_ring_ag_trace_schema_and_bit_identity():
    out = run_devices(_RING_TRACE_SCRIPT, devices=4)
    assert "RING_TRACE_OK" in out


_FUSED_BARRIER_SCRIPT = r"""
import functools
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro import obs, ops
from repro.core.collective_matmul import make_sharded

W = jax.device_count()
assert W == 4, W
mesh = jax.make_mesh((W,), ("tp",))
specs = ((P(None, "tp"), P("tp", None), P(None, "tp"), P("tp", None)),
         P(None, "tp"))
rng = np.random.RandomState(0)
m, k, n, f = 16 * W, 8 * W, 8 * W, 8 * W
y, wo, wi, xr = (jnp.asarray(rng.randn(*s), jnp.float32)
                 for s in ((m, k), (k, n), (n, f), (m, n)))

def mid(r, x):  # the rank-local seam between the two halves
    return jnp.tanh(r + x)

def unfused(y, wo, wi, xr):
    r = ops.matmul_rs(y, wo, axis="tp", mode="ring", backend="kernel",
                      out_dtype=jnp.float32)
    return ops.ag_matmul(mid(r, xr), wi, axis="tp", mode="ring",
                         backend="kernel", out_dtype=jnp.float32)

fused = functools.partial(ops.matmul_rs_ag_matmul, axis="tp", mode="ring",
                          backend="kernel", out_dtype=jnp.float32, mid=mid)

obs.enable()  # before the first compile: executor spans are trace-gated

def barriers(fn):
    fn = make_sharded(fn, mesh, *specs)
    out = jax.block_until_ready(fn(y, wo, wi, xr))  # compile
    obs.clear()
    jax.block_until_ready(fn(y, wo, wi, xr))
    ev = obs.events(clear=True)
    assert ev, "no trace events: kernel backend not engaged?"
    return np.asarray(out), sum(e.kind == "barrier" for e in ev)

out_u, nb_u = barriers(unfused)
out_f, nb_f = barriers(fused)
np.testing.assert_allclose(out_f, out_u, rtol=1e-4, atol=1e-4)
# the chain drops the pair's rs-exit and ag-entry rendezvous: two
# barriers on every PE
assert nb_f == nb_u - 2 * W, (nb_u, nb_f, W)
print("FUSED_BARRIERS_OK", nb_u, nb_f)
"""


def test_fused_boundary_drops_two_barriers_per_pe():
    """The chained ``push_rs_ring_ag`` protocol records exactly 2*W fewer
    ``barrier`` events than the back-to-back ``matmul_rs`` ->
    ``ag_matmul`` kernel pair, on the same values."""
    out = run_devices(_FUSED_BARRIER_SCRIPT, devices=4)
    assert "FUSED_BARRIERS_OK" in out


# ---------------------------------------------------------------------------
# Metrics pinned on a hand-built synthetic timeline (no devices needed)
# ---------------------------------------------------------------------------


def test_overlap_efficiency_synthetic():
    from repro import obs

    # 2 PEs, wall = 10s. PE0: 6s compute + 2s arrival stall; PE1: 5s
    # compute + 4s credit stall. exposed = mean(2, 4) = 3 -> eff = 0.7.
    ev = [
        obs.TraceEvent(0, 7, "tile_compute", "s0", 0, 0.0, 6.0),
        obs.TraceEvent(0, 7, "arrival_wait", "recv", 0, 6.0, 8.0),
        obs.TraceEvent(0, 7, "put", "ws->pe1", 1024, 8.0, 10.0),
        obs.TraceEvent(1, 7, "credit_wait", "cap", 0, 0.0, 4.0),
        obs.TraceEvent(1, 7, "tile_compute", "s0", 0, 4.0, 9.0),
        obs.TraceEvent(1, 7, "put", "ws->pe0", 1024, 9.0, 10.0),
    ]
    s = obs.metrics.summarize(ev, op="synthetic")
    assert s.wall == pytest.approx(10.0)
    assert s.compute_busy == pytest.approx(5.5)     # mean(6, 5)
    assert s.exposed_comm == pytest.approx(3.0)     # mean(2, 4)
    assert s.stall_frac == pytest.approx(0.3)
    assert s.overlap_efficiency == pytest.approx(0.7)
    assert s.wire_bytes == 2048
    assert s.n_pes == 2 and s.n_events == 6
    assert s.per_pe[0]["stall"] == pytest.approx(2.0)
    assert s.per_pe[1]["stall"] == pytest.approx(4.0)


def test_mid_stream_barriers_count_as_exposed():
    from repro import obs

    # One PE, one kernel instance (cid 7): entry barrier, a mid-stream
    # flush, an exit barrier. Only the FIRST is the launch rendezvous
    # (the separate `barrier` bucket); the later two are rendezvous the
    # schedule put in the middle of the work — exposed comm (this is
    # what makes the fused rs->ag chain read better than the
    # back-to-back pair: it drops the mid-chain flushes).
    ev = [
        obs.TraceEvent(0, 7, "barrier", "b", 0, 0.0, 1.0),   # launch
        obs.TraceEvent(0, 7, "tile_compute", "s0", 0, 1.0, 5.0),
        obs.TraceEvent(0, 7, "barrier", "b", 0, 5.0, 7.0),   # flush
        obs.TraceEvent(0, 7, "tile_compute", "s1", 0, 7.0, 9.0),
        obs.TraceEvent(0, 7, "barrier", "b", 0, 9.0, 10.0),  # flush
    ]
    s = obs.metrics.summarize(ev)
    assert s.barrier == pytest.approx(1.0)
    assert s.exposed_comm == pytest.approx(3.0)
    assert s.stall_frac == pytest.approx(0.3)
    assert s.overlap_efficiency == pytest.approx(0.7)
    # a second kernel instance gets its own launch rendezvous
    ev2 = ev + [obs.TraceEvent(0, 8, "barrier", "b", 0, 10.0, 12.0)]
    s2 = obs.metrics.summarize(ev2)
    assert s2.barrier == pytest.approx(3.0)
    assert s2.exposed_comm == pytest.approx(3.0)
    # unsorted input: the launch barrier is the EARLIEST, not the first
    # in list order
    s3 = obs.metrics.summarize(list(reversed(ev)))
    assert s3.barrier == pytest.approx(1.0)
    assert s3.exposed_comm == pytest.approx(3.0)


def test_summarize_empty_trace_raises():
    from repro import obs

    with pytest.raises(ValueError, match="empty trace"):
        obs.metrics.summarize([])


def test_split_by_cid():
    from repro import obs

    ev = [obs.TraceEvent(0, 1, "put", "a", 1, 0.0, 1.0),
          obs.TraceEvent(0, 2, "put", "b", 1, 0.0, 1.0),
          obs.TraceEvent(1, 1, "read", "a", 1, 0.0, 1.0)]
    groups = obs.metrics.split_by_cid(ev)
    assert sorted(groups) == [1, 2]
    assert len(groups[1]) == 2 and len(groups[2]) == 1


# ---------------------------------------------------------------------------
# Stall watchdog: timeout resolved at wait time + report content
# ---------------------------------------------------------------------------


def test_watchdog_report_on_timeout(monkeypatch):
    from repro import obs
    from repro.shmem import emulated as em

    # satellite 1: the timeout is read PER WAIT — this setenv takes
    # effect without any reimport (at import time the default was 60s,
    # so this test hanging <1s proves wait-time resolution)
    monkeypatch.setenv("REPRO_SHMEM_TIMEOUT", "0.2")
    key = (9301, 1)
    obs.enable()
    try:
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError) as ei:
            em._host_wait(key, "recv", np.int32(0), np.int32(2), np.int32(1))
        elapsed = time.perf_counter() - t0
        assert elapsed < 5.0, f"timeout not resolved at wait time ({elapsed})"
        msg = str(ei.value)
        assert "timed out" in msg
        assert "shmem watchdog" in msg
        assert "pe 2: wait on 'recv' want=1 have=0" in msg
    finally:
        obs.disable()
        em.reset(key[0])


def test_watchdog_reports_other_waiters(monkeypatch):
    from repro.shmem import emulated as em

    # per-wait timeout resolution lets the two waits use different
    # budgets: the blocker outlives the probing wait, so the probe's
    # watchdog report captures it in the waiter table
    monkeypatch.setenv("REPRO_SHMEM_TIMEOUT", "30")
    key = (9302, 1)
    try:
        blocker = threading.Thread(
            target=lambda: em._host_wait(key, "cap", np.int32(0), np.int32(0),
                                         np.int32(3)),
            daemon=True)
        blocker.start()
        w = em._world(key)
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            with w.cond:
                if 0 in w.waiters:
                    break
            time.sleep(0.005)
        monkeypatch.setenv("REPRO_SHMEM_TIMEOUT", "0.2")
        with pytest.raises(RuntimeError) as ei:
            em._host_wait(key, "recv", np.int32(0), np.int32(1), np.int32(1))
        # the report names BOTH blocked PEs (credit waiter + this one)
        msg = str(ei.value)
        assert "pe 0: wait on 'cap' want=3 have=0" in msg
        assert "pe 1: wait on 'recv' want=1 have=0" in msg
        # release the blocker (grant its 3 credits) and clean up
        em._host_signal(key, "cap", np.int32(0), np.int32(0), np.int32(3),
                        np.int32(1))
        blocker.join(timeout=5.0)
        assert not blocker.is_alive()
    finally:
        em.reset(key[0])


# ---------------------------------------------------------------------------
# reset() guard: refuses to drop state under a blocked PE (satellite 2)
# ---------------------------------------------------------------------------


def test_reset_refuses_while_wait_in_flight(monkeypatch):
    from repro.shmem import emulated as em

    monkeypatch.setenv("REPRO_SHMEM_TIMEOUT", "30")
    key = (9303, 1)
    done = threading.Event()

    def blocked_wait():
        em._host_wait(key, "recv", np.int32(0), np.int32(0), np.int32(1))
        done.set()

    t = threading.Thread(target=blocked_wait, daemon=True)
    t.start()
    w = em._world(key)
    deadline = time.perf_counter() + 5.0
    while time.perf_counter() < deadline:
        with w.cond:
            if 0 in w.waiters:
                break
        time.sleep(0.005)
    with pytest.raises(RuntimeError, match="wait in flight"):
        em.reset(key[0])
    # the error names the live waiter via the watchdog table
    with pytest.raises(RuntimeError, match="pe 0: wait on 'recv'"):
        em.reset(key[0])
    # release the waiter; reset then succeeds
    em._host_signal(key, "recv", np.int32(0), np.int32(0), np.int32(1),
                    np.int32(1))
    assert done.wait(timeout=5.0)
    t.join(timeout=5.0)
    em.reset(key[0])
    with em._worlds_lock:
        assert key not in em._worlds


def test_reset_drops_trace_buffers():
    from repro import obs
    from repro.shmem import emulated as em

    key = (9304, 1)
    obs.enable()
    try:
        em._host_signal(key, "s", np.int32(0), np.int32(0), np.int32(1),
                        np.int32(0))
        assert any(ev.cid == key[0] for ev in obs.events())
        em.reset(key[0])
        assert not any(ev.cid == key[0] for ev in obs.events())
    finally:
        obs.disable()
        em.reset(key[0])


# ---------------------------------------------------------------------------
# Disabled mode: no events, no measurable overhead on the host-op path
# ---------------------------------------------------------------------------


def test_disabled_records_nothing_and_is_cheap():
    from repro import obs
    from repro.shmem import emulated as em

    assert not obs.enabled()
    key = (9305, 1)
    try:
        n = 2000
        t0 = time.perf_counter()
        for i in range(n):
            em._host_signal(key, "s", np.int32(0), np.int32(0), np.int32(1),
                            np.int32(0))
        per_call = (time.perf_counter() - t0) / n
        assert not obs.events(), "disabled tracing recorded events"
        # generous absolute bound: the gate is one bool check per call
        assert per_call < 1e-3, f"{per_call * 1e6:.1f}us per host op"
    finally:
        em.reset(key[0])


def test_capacity_bounds_ring_buffer():
    from repro import obs
    from repro.shmem import emulated as em

    key = (9306, 1)
    obs.enable(capacity=16)
    try:
        for _ in range(100):
            em._host_signal(key, "s", np.int32(0), np.int32(0), np.int32(1),
                            np.int32(0))
        mine = [ev for ev in obs.events() if ev.cid == key[0]]
        assert len(mine) == 16, len(mine)
    finally:
        obs.disable()
        obs.enable()  # restore default capacity for later tests
        obs.disable()
        em.reset(key[0])


# ---------------------------------------------------------------------------
# Chrome-trace export shape
# ---------------------------------------------------------------------------


def test_chrome_trace_metadata_and_units():
    from repro import obs

    ev = [obs.TraceEvent(2, 5, "tile_compute", "s0", 0, 1.0, 1.001),
          obs.TraceEvent(2, 5, "put", "ws->pe0", 64, 1.001, 1.002)]
    doc = obs.trace.chrome_trace(ev)
    meta = [r for r in doc["traceEvents"] if r["ph"] == "M"]
    names = {(r["name"], r.get("tid")) for r in meta}
    assert ("process_name", None) in names
    assert ("thread_name", 2) in names
    xs = [r for r in doc["traceEvents"] if r["ph"] == "X"]
    assert xs[0]["ts"] == pytest.approx(0.0)          # normalized to start
    assert xs[0]["dur"] == pytest.approx(1000.0)      # 1ms in us
    assert xs[1]["args"]["bytes"] == 64


def test_trace_save_writes_file(tmp_path):
    from repro import obs

    path = tmp_path / "t.json"
    ev = [obs.TraceEvent(0, 1, "put", "ws", 4, 0.0, 1.0)]
    n = obs.trace.save(str(path), ev)
    assert n == 1
    import json

    doc = json.loads(path.read_text())
    assert doc["traceEvents"]
