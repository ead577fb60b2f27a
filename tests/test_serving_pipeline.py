"""Pipelined serving drain invariants (``repro.serve``, PR 9).

The load-bearing claims, each pinned here:
  * the depth-2 pipelined drain is BITWISE equal to the synchronous
    reference (``inflight=1``) under join/leave/swap churn mid-pipeline
    — same per-robot poses, same surviving state rows, one chunk trace;
  * the in-flight deque respects its bound and ``flush()`` drains the
    tail (``run_until_drained`` never drops tail poses);
  * staging sets are written-once: an in-flight set is write-protected
    (numpy write lock) and over-acquiring raises ``StagingOverrun``,
    as does resizing with chunks in flight;
  * the gather serves high-``priority`` robots first when
    ``gather_budget`` cannot drain everything;
  * latency accounting stamps poses at the actual drain point and
    splits queue wait (arrival->dispatch) from pipeline residence;
  * the host spans run on the engine's clock and nest inside the
    dispatch, and the work counters sum only real frames.
"""
import numpy as np
import pytest

from repro.serve import (RobotStatePool, ServingEngine, StagingOverrun)


@pytest.fixture(scope="module")
def pool_pair(synthetic_sequence, small_cfg):
    """Two identical capacity-3 pools — one driven synchronously, one
    pipelined — shared across the module: chunk dispatches compile once
    per pool, and every test drains/retires what it admits."""
    seq = synthetic_sequence
    mk = lambda: RobotStatePool(small_cfg, seq.cam, capacity=3,
                                window=8, staging_depth=2)
    return mk(), mk()


def _drain_pools(pool_pair):
    for pool in pool_pair:
        for rid in list(pool.robot_ids):
            pool.retire(rid)


def _frame(seq, i):
    """Single frame i as ``submit_frame`` arguments."""
    ipf = seq.imu_per_frame
    lo, hi = max(i - 1, 0) * ipf, max(i, 1) * ipf
    return (seq.images_left[i], seq.images_right[i],
            seq.imu_accel[lo:hi], seq.imu_gyro[lo:hi], seq.gps[i])


def _mk_engines(pool_pair, chunk=2, dt=1e-3, **kw):
    sync_pool, pipe_pool = pool_pair
    return (ServingEngine(sync_pool, chunk=chunk, dt_imu=dt,
                          overflow="reject", inflight=1, **kw),
            ServingEngine(pipe_pool, chunk=chunk, dt_imu=dt,
                          overflow="reject", inflight=2, **kw))


# ---------------------------------------------------------------------------
# the flagship equivalence: pipelined == synchronous, bitwise, under churn
# ---------------------------------------------------------------------------
def _drive_both(ops, engines, seq, dt, tag):
    """Apply one churn script to both engines boundary-by-boundary and
    assert the pipelined run is bitwise identical to the synchronous
    one. ``ops`` is a list of (kind, robot 0..3, scenario) tuples;
    every 3 ops close a chunk boundary (frames staged, run_chunk)."""
    sync_eng, pipe_eng = engines
    joined, cursor = set(), {}
    out = {0: {}, 1: {}}

    def collect(k, poses):
        for rid, p in poses.items():
            out[k].setdefault(rid, []).append(p)

    def boundary():
        for rid in sorted(joined):
            n = min(2, 14 - cursor[rid])
            for j in range(n):
                fr = _frame(seq, cursor[rid] + j)
                sync_eng.submit_frame(rid, *fr)
                pipe_eng.submit_frame(rid, *fr)
            cursor[rid] += n
        collect(0, sync_eng.run_chunk())
        collect(1, pipe_eng.run_chunk())
        # the depth bound holds BETWEEN calls: at most inflight-1 held
        assert pipe_eng.inflight_chunks() <= pipe_eng.inflight - 1
        assert sync_eng.inflight_chunks() == 0

    for i, (kind, r, scen) in enumerate(ops):
        rid = f"{tag}r{r}"
        if kind == "join" and rid not in joined:
            for eng in engines:
                eng.submit_join(rid, scen, priority=r % 2)
            joined.add(rid)
            cursor.setdefault(rid, 0)
        elif kind == "leave" and rid in joined:
            for eng in engines:
                eng.submit_leave(rid)
            joined.discard(rid)
        elif kind == "swap" and rid in joined:
            for eng in engines:
                eng.submit_assign(rid, scen)
        if i % 3 == 2:
            boundary()
    # churn exhausted: steady-state frame-only boundaries, where the
    # depth-2 pipeline genuinely overlaps (no request-drain bubbles)
    for _ in range(3):
        boundary()
    collect(0, sync_eng.flush())
    collect(1, pipe_eng.flush())
    assert sync_eng.inflight_chunks() == pipe_eng.inflight_chunks() == 0

    # identical drained poses, bitwise, robot by robot
    assert set(out[0]) == set(out[1])
    for rid in out[0]:
        a = np.concatenate(out[0][rid])
        b = np.concatenate(out[1][rid])
        assert np.array_equal(a, b), rid

    # identical surviving state rows, bitwise
    sync_pool, pipe_pool = sync_eng.pool, pipe_eng.pool
    assert sync_pool.robot_ids == pipe_pool.robot_ids
    for rid in sync_pool.robot_ids:
        a = sync_pool.state_row(sync_pool.ticket_of(rid))
        b = pipe_pool.state_row(pipe_pool.ticket_of(rid))
        for name in ("p", "v", "q", "P"):
            assert np.array_equal(getattr(a.filt, name),
                                  getattr(b.filt, name)), (rid, name)
        assert np.array_equal(a.frame_idx, b.frame_idx), rid
    assert sync_pool.chunk_trace_count() == 1
    assert pipe_pool.chunk_trace_count() == 1
    assert pipe_eng.peak_inflight <= pipe_eng.inflight


def test_pipelined_bitwise_equals_sync_churn_fuzz(pool_pair,
                                                  synthetic_sequence):
    """Random join/leave/swap churn mid-pipeline — hypothesis-driven
    when available, seeded numpy otherwise. The Registration scenario
    rides along so the fuzz crosses the needs_flush immediate-drain
    path, and priorities alternate so the gather order is exercised."""
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    _drain_pools(pool_pair)
    scens = ["vio", "slam", "registration"]

    def run_example(ops, tag):
        _drain_pools(pool_pair)
        _drive_both(ops, _mk_engines(pool_pair, dt=dt), seq, dt, tag)

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        rng = np.random.RandomState(0)
        kinds = ["join", "leave", "swap"]
        for ex in range(6):
            ops = [(kinds[rng.randint(3)], int(rng.randint(4)),
                    scens[rng.randint(3)])
                   for _ in range(rng.randint(3, 15))]
            run_example(ops, f"e{ex}")
        return

    ops_st = st.lists(
        st.tuples(st.sampled_from(["join", "leave", "swap"]),
                  st.integers(0, 3), st.sampled_from(scens)),
        min_size=3, max_size=14)
    counter = iter(range(10**6))

    @settings(max_examples=6, deadline=None)
    @given(ops_st)
    def run(ops):
        run_example(ops, f"h{next(counter)}")

    run()


# ---------------------------------------------------------------------------
# pipeline mechanics: depth bound, flush, staging write-once
# ---------------------------------------------------------------------------
def test_flush_drains_tail_and_run_until_drained(pool_pair,
                                                 synthetic_sequence):
    """At depth 2, run_chunk returns poses one chunk behind; the tail
    lives in the deque until flush(). run_until_drained must wait for
    the deque (tail poses are never dropped)."""
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    _drain_pools(pool_pair)
    _, eng = _mk_engines(pool_pair, dt=dt)
    eng.submit_join("f0")
    for i in range(2):
        eng.submit_frame("f0", *_frame(seq, i))
    first = eng.run_chunk()
    # chunk 1 dispatched, still in flight: nothing drained yet
    assert first == {} and eng.inflight_chunks() == 1
    tail = eng.flush()
    assert eng.inflight_chunks() == 0
    assert tail["f0"].shape == (2, 3)

    # run_until_drained: 4 frames -> 2 chunks; every pose comes back
    for i in range(4):
        eng.submit_frame("f0", *_frame(seq, 2 + i))
    out = eng.run_until_drained()
    assert out["f0"].shape == (4, 3)
    assert eng.inflight_chunks() == 0 and eng.pending_frames() == 0
    eng.submit_leave("f0")
    eng.run_chunk()


def test_staging_write_protect_and_overrun(pool_pair,
                                           synthetic_sequence):
    """Written-once staging: an in-flight set rejects host writes
    (numpy write lock), acquiring past ``staging_depth`` raises
    ``StagingOverrun``, and so does resizing mid-pipeline."""
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    _drain_pools(pool_pair)
    pool = pool_pair[1]
    pool.admit("s0")

    def stage(i0):
        ipf = seq.imu_per_frame
        fr = (seq.images_left[i0:i0 + 2], seq.images_right[i0:i0 + 2],
              np.stack([seq.imu_accel[max(i - 1, 0) * ipf:max(i, 1) * ipf]
                        for i in range(i0, i0 + 2)]),
              np.stack([seq.imu_gyro[max(i - 1, 0) * ipf:max(i, 1) * ipf]
                        for i in range(i0, i0 + 2)]),
              seq.gps[i0:i0 + 2])
        return pool.dispatch_chunk({"s0": fr}, dt, chunk=2)

    fl1 = stage(0)
    assert pool.staging_in_flight() == 1
    with pytest.raises(ValueError):
        fl1.staging.il[0, 0] = 0.0         # write-protected in flight
    fl2 = stage(2)                          # second set: still fine
    assert pool.staging_in_flight() == 2
    with pytest.raises(StagingOverrun):
        pool.acquire_staging(2, seq.imu_per_frame)
    with pytest.raises(StagingOverrun):
        pool.resize(5)                      # mid-pipeline growth
    # FIFO drain releases the sets for reuse
    p1 = pool.drain_chunk(fl1)
    p2 = pool.drain_chunk(fl2)
    assert p1["s0"].shape == p2["s0"].shape == (2, 3)
    assert pool.staging_in_flight() == 0
    fl1.staging.il[0, 0] = 0.0              # writable again
    assert pool.chunk_trace_count() == 1
    pool.retire("s0")


def test_priority_gather_order(pool_pair, synthetic_sequence):
    """With a gather budget smaller than the queued frames, the
    high-priority robot's frames dispatch first; the low-priority
    robot's wait in FIFO order for the next boundary."""
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    _drain_pools(pool_pair)
    _, eng = _mk_engines(pool_pair, dt=dt, gather_budget=2)
    eng.submit_join("lo", priority=0)
    eng.submit_join("hi", priority=5)
    for i in range(2):
        eng.submit_frame("lo", *_frame(seq, i))
        eng.submit_frame("hi", *_frame(seq, i))
    eng.run_chunk()
    poses = eng.flush()
    # budget 2 == one robot's frames: hi went first, lo still queued
    assert set(poses) == {"hi"} and poses["hi"].shape == (2, 3)
    assert eng.pending_frames("lo") == 2
    out = eng.run_until_drained()
    assert out["lo"].shape == (2, 3)
    for rid in ("lo", "hi"):
        eng.submit_leave(rid)
    eng.run_chunk()


def test_latency_split_and_report(pool_pair, synthetic_sequence):
    """Latency is stamped at the DRAIN point (not dispatch): with a
    fake clock, total latency = drain tick - submit tick, and the
    queue-wait component = dispatch tick - submit tick. The report
    carries the stage/dispatch/sync/host-stage decomposition."""
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    _drain_pools(pool_pair)
    tick = [0.0]

    def clock():
        tick[0] += 1.0
        return tick[0]

    eng = ServingEngine(pool_pair[1], chunk=2, dt_imu=dt,
                        overflow="reject", inflight=2, clock=clock)
    eng.submit_join("t0")
    eng.submit_frame("t0", *_frame(seq, 0))
    eng.run_chunk()       # dispatches, holds the chunk in flight
    assert eng.latencies["t0"] == [] and len(eng.queue_waits["t0"]) == 1
    eng.flush()
    assert len(eng.latencies["t0"]) == 1
    # drain happened strictly after dispatch: total > queue wait >= 0
    assert eng.latencies["t0"][0] > eng.queue_waits["t0"][0] >= 0.0

    rep = eng.latency_report()
    assert rep["inflight"] == 2 and rep["peak_inflight"] >= 1
    assert set(rep["decomposition"]) == {
        "stage", "dispatch", "build", "h2d", "enqueue", "feedback",
        "sync", "host_stage", "queue_wait", "in_flight"}
    assert rep["decomposition"]["sync"]["count"] == 1
    r = rep["per_robot"]["t0"]
    assert r["frames"] == 1
    assert r["p50_s"] >= r["queue_wait"]["p50_s"]
    assert r["in_pipeline"]["p50_s"] >= 0.0
    eng.submit_leave("t0")
    eng.run_chunk()


def _ticking_clock(start=0.0):
    tick = [start]

    def clock():
        tick[0] += 1.0
        return tick[0]
    return clock


def test_host_spans_split_dispatch(pool_pair, synthetic_sequence):
    """Every host span runs on the engine's clock: the dispatch's parts
    (build, h2d, enqueue, and feedback for a Registration chunk) sum to
    at most the dispatch, and stage, dispatch, sync and host stage keep
    one sample per chunk."""
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    _drain_pools(pool_pair)
    eng = ServingEngine(pool_pair[1], chunk=2, dt_imu=dt,
                        overflow="reject", inflight=2,
                        clock=_ticking_clock())
    eng.submit_join("v0", "vio")
    eng.submit_join("g0", "registration")
    for i in range(3):
        eng.submit_frame("v0", *_frame(seq, i))
        if i == 1:
            eng.submit_frame("g0", *_frame(seq, i))
        eng.run_chunk()
    eng.flush()
    d = {k: tr.samples for k, tr in eng.decomp.items()}
    assert eng.dispatches == 3
    for k in ("stage", "dispatch", "build", "h2d", "enqueue", "sync",
              "host_stage"):
        assert len(d[k]) == 3, k
    # only the chunk holding the Registration frame paid feedback
    assert len(d["feedback"]) == 1 == eng.flushes
    feedback = [0.0, d["feedback"][0], 0.0]
    for i in range(3):
        parts = d["build"][i] + d["h2d"][i] + d["enqueue"][i] + feedback[i]
        assert 0.0 < parts <= d["dispatch"][i]
    assert len(d["queue_wait"]) == len(d["in_flight"]) == 4   # frames
    for rid in ("v0", "g0"):
        eng.submit_leave(rid)
    eng.run_chunk()


def test_arrival_stamp_drives_waits(pool_pair, synthetic_sequence):
    """``submit_frame(..., t=)`` stamps the frame's arrival: its queue
    wait and latency count from ``t``, and their difference is the
    frame's in-flight time (dispatch-to-sync)."""
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    _drain_pools(pool_pair)
    eng = ServingEngine(pool_pair[1], chunk=2, dt_imu=dt,
                        overflow="reject", inflight=2,
                        clock=_ticking_clock(100.0))
    eng.submit_join("a0")
    eng.submit_frame("a0", *_frame(seq, 0), t=5.0)    # arrived long ago
    eng.submit_frame("a0", *_frame(seq, 1))           # arrives now
    eng.run_chunk()
    eng.flush()
    (q_old, q_now), (l_old, l_now) = (eng.queue_waits["a0"],
                                      eng.latencies["a0"])
    assert q_old > 95.0 > q_now >= 0.0
    assert q_old - q_now == l_old - l_now > 95.0
    in_flight = eng.decomp["in_flight"].samples
    assert in_flight == [l_old - q_old] * 2 and in_flight[0] > 0.0
    assert eng.decomp["queue_wait"].samples == [q_old, q_now]
    eng.submit_leave("a0")
    eng.run_chunk()


def test_counters_cover_only_real_frames(pool_pair, synthetic_sequence,
                                         monkeypatch):
    """The work counters sum each robot's real frames of its manifest
    slot: values planted in padding frames and free slots never reach
    them."""
    import jax.numpy as jnp
    from repro.core.step import COUNTS
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    _drain_pools(pool_pair)
    pool = pool_pair[1]
    fleet = pool.fleet
    real, raw = fleet._fused_fleet_chunk, []

    def chunk(states, inputs, flags, dt_imu):
        act = np.asarray(inputs.active)     # the inputs are donated
        states, outs = real(states, inputs, flags, dt_imu)
        raw.append((act, np.asarray(outs.counts)))
        junk = jnp.where(act[..., None], 0, 1000)
        return states, outs._replace(counts=outs.counts + junk)
    monkeypatch.setattr(fleet, "_fused_fleet_chunk", chunk)
    eng = ServingEngine(pool, chunk=2, dt_imu=dt, overflow="reject",
                        inflight=2)
    eng.submit_join("c0")
    for i in range(5):                      # chunks of 1 and 2 frames
        eng.submit_frame("c0", *_frame(seq, i))
        if i % 2 == 0:
            eng.run_chunk()
    eng.flush()
    want = sum(c[act].sum(axis=0) for act, c in raw)
    got = eng.counters()
    assert [got[k] for k in COUNTS] == [int(v) for v in want]
    assert got["frames"] == 5 and got["flushes"] == 0
    assert got["tracks"] > 0 and max(got[k] for k in COUNTS) < 1000
    assert got["per_robot"]["c0"] == {k: v for k, v in got.items()
                                      if k not in ("flushes", "per_robot")}
    assert eng.latency_report()["counters"] == got
    eng.submit_leave("c0")
    eng.run_chunk()


def test_knob_validation(pool_pair):
    pool = pool_pair[0]                    # staging_depth == 2
    with pytest.raises(ValueError):
        ServingEngine(pool, inflight=0)
    with pytest.raises(ValueError):
        ServingEngine(pool, inflight=pool.staging_depth + 1)
    with pytest.raises(ValueError):
        ServingEngine(pool, gather_budget=0)
    with pytest.raises(ValueError):
        ServingEngine(pool, overflow="drop")


def test_resize_overflow_flushes_pipeline(synthetic_sequence, small_cfg):
    """overflow="resize" with chunks in flight: the engine drains the
    pipeline (returning the tail poses) before growing the pool, and
    the carried state matches — the resize guard never fires."""
    seq = synthetic_sequence
    dt = seq.dt / seq.imu_per_frame
    pool = RobotStatePool(small_cfg, seq.cam, capacity=1, window=8,
                          staging_depth=2)
    eng = ServingEngine(pool, chunk=2, dt_imu=dt, overflow="resize",
                        inflight=2)
    eng.submit_join("a")
    for i in range(2):
        eng.submit_frame("a", *_frame(seq, i))
    assert eng.run_chunk() == {}           # a's chunk now in flight
    assert eng.inflight_chunks() == 1
    eng.submit_join("b")                   # forces the slow path
    poses = eng.run_chunk()
    # the in-flight tail drained as part of the resize, not dropped
    assert poses["a"].shape == (2, 3)
    assert pool.capacity == 2 and pool.resizes == 1
    assert pool.occupancy == 2
    pool.check_invariants()
