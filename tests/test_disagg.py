"""Disaggregated trunk/head serving (`repro/serving/disagg.py`).

Three layers of contract:

  * `FeatureMapCache` — bounded LRU + TTL with single-flight dedup: exact
    hit/miss/coalesced accounting (each call counts exactly one), capacity
    and TTL evictions by reason, one trunk pass per thundering herd, and
    leader-failure re-election (a crashed leader never wedges a key).
  * `DisaggServer` correctness — window scores word-exact vs the
    monolithic `FcnSweep` on both fixed substrates (same ints, same
    dtype), detection parity, the fleet ledger invariant
    `submitted == served + shed + pending`, trunk failover onto a healthy
    sibling, all-faulted and deadline/queue_depth shed paths, and the
    `StreamingPipeline` seam (the server slots in where the sweep runs).
  * The slow soak — concurrent streams against a started fleet with a
    mid-run trunk fault and a cache sized BELOW the distinct-frame pool
    (constant churn): every ledger reconciles, cache memory stays bounded
    by construction, and the flight recorder holds the whole run.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import smallnet
from repro.serving.disagg import (DisaggServer, DisaggShedError,
                                  FeatureMapCache, FeatureMapKey,
                                  feature_key, frame_digest)
from repro.streaming.fcn_sweep import FcnSweep
from repro.streaming.sources import RepeatedClipSource, SyntheticVideoSource

BACKENDS = ("fixed", "fixed_pallas")


@pytest.fixture(scope="module")
def params():
    return smallnet.seeded_params()


@pytest.fixture(scope="module")
def clip():
    return SyntheticVideoSource(n_frames=6, seed=7).frames()


def _key(i: int) -> FeatureMapKey:
    return FeatureMapKey(digest=f"k{i}", backend="fixed", cfg="q16.16",
                         interpret=True)


def _quad(i: int):
    return tuple(np.full((2, 2), i + j, np.int32) for j in range(4))


# ---------------------------------------------------------------------------
# FeatureMapCache
# ---------------------------------------------------------------------------

class TestFeatureMapCache:
    def test_lru_capacity_eviction(self):
        c = FeatureMapCache(capacity=2)
        c.put(_key(0), _quad(0))
        c.put(_key(1), _quad(1))
        assert c.get(_key(0)) is not None       # 0 now most-recent
        c.put(_key(2), _quad(2))                # evicts 1 (LRU), not 0
        assert c.get(_key(1)) is None
        assert c.get(_key(0)) is not None
        assert len(c) == 2
        assert c.stats()["evictions"]["capacity"] == 1

    def test_ttl_expiry_is_lazy_and_counted(self):
        c = FeatureMapCache(capacity=4, ttl_s=0.02)
        c.put(_key(0), _quad(0))
        assert c.get(_key(0)) is not None
        time.sleep(0.03)
        assert c.get(_key(0)) is None
        s = c.stats()
        assert s["evictions"]["ttl"] == 1
        assert s["entries"] == 0

    def test_each_call_counts_exactly_one_outcome(self):
        c = FeatureMapCache(capacity=4)
        calls = []
        for _ in range(5):
            c.get_or_compute(_key(0), lambda: calls.append(1) or _quad(0))
        s = c.stats()
        assert len(calls) == 1
        assert s["misses"] == 1 and s["hits"] == 4 and s["coalesced"] == 0
        assert s["hit_rate"] == pytest.approx(0.8)

    def test_single_flight_one_trunk_pass_per_herd(self):
        c = FeatureMapCache(capacity=4)
        computes, gate = [], threading.Event()

        def compute():
            gate.wait(timeout=5.0)
            computes.append(1)
            return _quad(0)

        results = []
        threads = [threading.Thread(
            target=lambda: results.append(c.get_or_compute(_key(0), compute)))
            for _ in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.05)          # let every follower park on the leader
        gate.set()
        for t in threads:
            t.join(timeout=10.0)
        s = c.stats()
        assert len(computes) == 1
        assert len(results) == 8
        assert s["misses"] == 1
        assert s["hits"] + s["coalesced"] == 7

    def test_leader_failure_wakes_followers_to_reelect(self):
        c = FeatureMapCache(capacity=4)
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) == 1:
                raise RuntimeError("leader died")
            return _quad(0)

        errors, values = [], []

        def call():
            try:
                values.append(c.get_or_compute(_key(0), flaky))
            except RuntimeError as e:
                errors.append(e)

        threads = [threading.Thread(target=call) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        # exactly one caller saw the crash; the rest re-elected and served
        assert len(errors) == 1
        assert len(values) == 3
        assert len(attempts) == 2

    def test_follower_timeout_raises(self):
        c = FeatureMapCache(capacity=4)
        started = threading.Event()

        def slow():
            started.set()
            time.sleep(0.5)
            return _quad(0)

        leader = threading.Thread(
            target=lambda: c.get_or_compute(_key(0), slow))
        leader.start()
        assert started.wait(timeout=5.0)
        with pytest.raises(TimeoutError):
            c.get_or_compute(_key(0), slow, timeout=0.02)
        leader.join(timeout=10.0)

    def test_bytes_gauge_tracks_resident_quads(self):
        c = FeatureMapCache(capacity=2)
        c.put(_key(0), _quad(0))
        per_entry = c.stats()["resident_bytes"]
        assert per_entry == sum(m.nbytes for m in _quad(0))
        c.put(_key(1), _quad(1))
        c.put(_key(2), _quad(2))      # capacity eviction keeps bytes flat
        s = c.stats()
        assert s["resident_bytes"] == 2 * per_entry
        assert s["resident_bytes_hwm"] <= 2 * per_entry


# ---------------------------------------------------------------------------
# Cache keying
# ---------------------------------------------------------------------------

def test_feature_key_separates_every_word_axis(clip):
    from repro.core import backends as B
    px = clip[0].pixels[None]
    fixed, ref = B.get_backend("fixed"), B.get_backend("ref")
    k = feature_key(px, fixed)
    assert k != feature_key(px, ref)                   # backend axis
    assert k != feature_key(clip[1].pixels[None], fixed)  # pixels
    assert k == feature_key(np.array(px), fixed)       # content, not id


def test_frame_digest_covers_shape_and_dtype():
    a = np.zeros((1, 8, 8, 1), np.float32)
    assert frame_digest(a) == frame_digest(a.copy())
    assert frame_digest(a) != frame_digest(a.reshape(1, 4, 16, 1))
    assert frame_digest(a) != frame_digest(a.astype(np.float64))


# ---------------------------------------------------------------------------
# DisaggServer: word-exactness + ledger
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_scores_word_exact_vs_monolithic_sweep(params, clip, backend):
    import jax
    sweep = FcnSweep(stride=8)
    srv = DisaggServer(params, backend=backend, stride=8, cache_capacity=8)
    for f in clip[:3]:
        mono = np.asarray(jax.device_get(
            sweep.score(params, f.pixels[None], backend=backend)))
        dis = np.asarray(srv.score_frame(f.pixels[None]))
        assert dis.dtype == mono.dtype
        assert np.array_equal(dis, mono)
        assert sweep.aggregate(mono, list(srv.positions)) \
            == srv.detect(f, tiler=sweep)


def test_repeat_queries_hit_the_cache_and_ledger_reconciles(params, clip):
    srv = DisaggServer(params, backend="fixed", stride=8, cache_capacity=8)
    first = srv.score_frame(clip[0].pixels[None])
    again = srv.score_frame(clip[0].pixels[None])
    assert np.array_equal(first, again)
    s = srv.stats()
    assert s["accounted"] and s["n"] == 2 and s["shed"] == 0
    assert s["cache"]["hits"] == 1 and s["cache"]["misses"] == 1
    # the hit ran NO trunk pass: only one stage request reached the pool
    trunk_served = sum(s["per_stage"][e.name]["n"] for e in srv.trunks)
    assert trunk_served == 1


def test_trunk_failover_to_healthy_sibling(params, clip):
    srv = DisaggServer(params, backend="fixed", stride=8, n_trunk=2)
    boom = RuntimeError("injected trunk fault")
    srv.trunks[0]._compute = lambda payload: (_ for _ in ()).throw(boom)
    for f in clip[:4]:
        scores = srv.score_frame(f.pixels[None])
        assert scores.shape[0] == len(srv.positions)
    s = srv.stats()
    assert s["accounted"] and s["n"] == 4 and s["shed"] == 0
    faults = sum(st["shed_by_reason"].get("fault", 0)
                 for st in s["per_stage"].values())
    assert faults >= 1, "the faulty trunk was never exercised"


def test_all_trunks_faulted_sheds_with_reason(params, clip):
    srv = DisaggServer(params, backend="fixed", stride=8, n_trunk=2)
    for eng in srv.trunks:
        eng._compute = lambda payload: (_ for _ in ()).throw(
            RuntimeError("boom"))
    with pytest.raises(DisaggShedError) as ei:
        srv.score_frame(clip[0].pixels[None])
    assert ei.value.reason == "fault"
    s = srv.stats()
    assert s["accounted"] and s["shed_by_reason"] == {"fault": 1}


def test_deadline_shed_under_trunk_backpressure(params, clip):
    srv = DisaggServer(params, backend="fixed", stride=8, n_trunk=1,
                       trunk_floor_s=0.2)
    with pytest.raises(DisaggShedError) as ei:
        srv.score_frame(clip[0].pixels[None], deadline_ms=1.0)
    assert ei.value.reason == "deadline"
    assert srv.stats()["shed_by_reason"] == {"deadline": 1}


def test_open_loop_intake_bound_sheds_queue_depth(params, clip):
    srv = DisaggServer(params, backend="fixed", stride=8, max_queue=2)
    uids = [srv.submit(clip[i % len(clip)].pixels) for i in range(5)]
    shed = srv.pop_shed(uids)
    assert list(shed.values()) == ["queue_depth"] * 3
    srv.start()
    try:
        srv.wait([u for u in uids if u not in shed], timeout=30.0)
    finally:
        srv.stop(drain=True)
    s = srv.stats()
    assert s["accounted"] and s["n"] == 2


def test_open_loop_matches_sync_scores(params, clip):
    srv = DisaggServer(params, backend="fixed", stride=8)
    srv.start()
    try:
        uids = [srv.submit(f.pixels) for f in clip[:3]]
        srv.wait(uids, timeout=30.0)
        res = srv.pop_results(uids)
    finally:
        srv.stop(drain=True)
    ref = DisaggServer(params, backend="fixed", stride=8)
    for uid, f in zip(uids, clip[:3]):
        assert np.array_equal(res[uid].scores, ref.score_frame(f.pixels[None]))


def test_streaming_pipeline_drives_the_disagg_server(params):
    from repro.streaming.pipeline import StreamingPipeline
    base = SyntheticVideoSource(n_frames=4, seed=7)
    source = RepeatedClipSource(base, repeats=3)
    sweep = FcnSweep(stride=8, threshold=0.5)
    srv = DisaggServer(params, backend="fixed", stride=8, cache_capacity=8)
    pipe = StreamingPipeline(source, srv, sweep)
    results = pipe.run()
    ps, ss = pipe.stats(), srv.stats()
    assert len(results) == len(source)
    assert ps["accounted"] and ps["frames_served"] == len(source)
    assert ss["accounted"] and ss["n"] == len(source)
    assert ss["cache"]["hit_rate"] == pytest.approx(2 / 3)
    # repeated frames must produce identical detections
    by_px = {}
    for f, r in zip(source, results):
        key = frame_digest(f.pixels)
        by_px.setdefault(key, r.detections)
        assert by_px[key] == r.detections


# ---------------------------------------------------------------------------
# The soak: streams + failover + cache churn, every ledger tight
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_disagg_soak_streams_failover_and_cache_churn(params):
    from repro.obs import trace as T
    n_streams, per_stream = 4, 120
    pool = [f.pixels[None]
            for f in SyntheticVideoSource(n_frames=12, seed=7).frames()]
    capacity = 6            # BELOW the distinct pool: constant churn
    tr = T.enable(capacity=1 << 16)
    try:
        srv = DisaggServer(params, backend="fixed", stride=8,
                           n_trunk=2, n_head=2, cache_capacity=capacity)
        per_entry = srv.cache._nbytes(srv._run_trunk(pool[0]))
        for eng in srv.trunks + srv.heads:
            eng.start()
        client_shed = [0] * n_streams

        def stream(sid: int):
            rng = np.random.default_rng(sid)
            for i in range(per_stream):
                px = pool[int(rng.integers(0, len(pool)))]
                try:
                    srv.score_frame(px)
                except DisaggShedError:
                    client_shed[sid] += 1
                if sid == 0 and i == per_stream // 3:
                    # mid-run fault: one trunk replica dies; the fleet
                    # fails over and keeps serving
                    srv.trunks[0]._compute = lambda p: (_ for _ in ()).throw(
                        RuntimeError("injected soak fault"))

        threads = [threading.Thread(target=stream, args=(i,))
                   for i in range(n_streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        assert not any(t.is_alive() for t in threads)
        srv.stop(drain=True)

        s = srv.stats()
        # the fleet ledger reconciles exactly, client-side view included
        assert s["accounted"]
        assert s["submitted"] == n_streams * per_stream
        assert s["n"] + s["shed"] == s["submitted"] and s["pending"] == 0
        assert s["shed"] == sum(client_shed)
        assert s["n"] >= 0.9 * s["submitted"], s["shed_by_reason"]
        for name, st in s["per_stage"].items():
            assert st["accounted"], (name, st)
        # cache memory bounded by construction, with real churn observed
        cs = s["cache"]
        assert cs["entries"] <= capacity
        assert cs["resident_bytes_hwm"] <= capacity * per_entry
        assert cs["evictions"]["capacity"] > 0
        assert cs["hits"] > 0
        # the flight recorder held the whole run
        assert tr.recorder.evicted == 0
    finally:
        T.disable()
