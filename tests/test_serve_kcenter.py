"""Tests for the online k-center serving engine (serve/kcenter.py) and the
host-side insertion tail of ``stream_update`` (core/streaming.py).

The three serving contracts pinned here:
  * every served ``assign`` is **bitwise** ``ops.assign_nearest`` on the
    snapshot centers of its answering epoch — including under interleaved
    ingest that bumps epochs mid-query-stream;
  * dispatch operand signatures are a function of the (query-bucket,
    center-bucket) pair only: after warmup, ragged query sizes and epoch
    bumps add ZERO new signatures (spy-asserted);
  * covered-point ingest (the steady state) bumps no epoch and refreshes
    no cache.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stream_init, stream_result, stream_update
from repro.data import gau
from repro.data.source import HostSource
from repro.kernels import ops
from repro.serve import AssignTicket, KCenterService


def _clustered(n, k, d, seed=0):
    return gau(n, k, d=d, seed=seed)


def _offline(q, centers):
    i, d2 = ops.assign_nearest(jnp.asarray(q), jnp.asarray(centers))
    return np.asarray(i), np.asarray(d2)


# ---------------------------------------------------------------------------
# served parity: bitwise vs the offline op, per epoch
# ---------------------------------------------------------------------------

def test_served_assign_bitwise_parity_every_epoch():
    k, d = 8, 6
    rng = np.random.default_rng(0)
    with KCenterService(k, d, snapshot_history=True) as svc:
        # three ingests at growing scale: each forces doublings, so we see
        # several distinct epochs
        for scale, seed in ((1.0, 1), (10.0, 2), (100.0, 3)):
            svc.submit_points(_clustered(600, k, d, seed=seed) * scale)
            svc.drain(timeout=120)
            q = rng.normal(size=(33, d)).astype(np.float32) * scale
            res = svc.assign(q, timeout=60)
            centers = svc.snapshot_at(res.epoch)
            ri, rd = _offline(q, centers)
            assert np.array_equal(ri, res.idx)
            assert np.array_equal(rd, res.d2)
        assert svc.stats["epochs"] >= 2


def test_single_center_sketch_parity():
    # an isotropic blob collapses the doubling sketch to ONE center — the
    # m=1 distance dot lowers as a matvec, which assign_bucketed must
    # special-case to stay bitwise with the unbucketed reference
    k, d = 16, 16
    rng = np.random.default_rng(3)
    # k+1 unit-sphere points in high d: max pairwise distance < 2 × min,
    # so the bootstrap merge at 4r = 2·min keeps exactly one center
    pts = rng.normal(size=(k + 1, d)).astype(np.float32)
    pts /= np.linalg.norm(pts, axis=1, keepdims=True)
    with KCenterService(k, d) as svc:
        svc.submit_points(pts)
        svc.drain(timeout=120)
        epoch, centers, _ = svc.snapshot()
        assert centers.shape[0] == 1       # the degenerate regime under test
        q = rng.normal(size=(37, d)).astype(np.float32)
        res = svc.assign(q, timeout=60)
        ri, rd = _offline(q, centers)
        assert res.epoch == epoch
        assert np.array_equal(ri, res.idx)
        assert np.array_equal(rd, res.d2)


def test_parity_under_interleaved_ingest():
    """Epoch bumps racing a query stream: every answer must still be
    bitwise-correct for the centers of the epoch that answered it."""
    k, d = 8, 4
    rng = np.random.default_rng(1)
    with KCenterService(k, d, snapshot_history=True) as svc:
        svc.submit_points(_clustered(400, k, d, seed=0))
        svc.drain(timeout=120)

        stop = threading.Event()

        def feeder():
            scale = 1.0
            while not stop.is_set():
                # keep forcing center-set changes; wrap before f32 overflow
                scale = scale * 1.5 if scale < 1e12 else 1.0
                svc.submit_points(
                    _clustered(100, k, d, seed=7) * np.float32(scale))

        feed = threading.Thread(target=feeder, daemon=True)
        feed.start()
        try:
            for _ in range(40):
                q = rng.normal(size=(9, d)).astype(np.float32) * 10
                res = svc.assign(q, timeout=60)
                ri, rd = _offline(q, svc.snapshot_at(res.epoch))
                assert np.array_equal(ri, res.idx)
                assert np.array_equal(rd, res.d2)
        finally:
            stop.set()
            feed.join()
        svc.drain(timeout=120)
        assert svc.stats["epochs"] >= 2   # the race actually happened


# ---------------------------------------------------------------------------
# epoch discipline: steady state = zero invalidations
# ---------------------------------------------------------------------------

def test_covered_ingest_bumps_no_epoch_and_refreshes_no_cache():
    k, d = 8, 6
    with KCenterService(k, d) as svc:
        svc.submit_points(_clustered(800, k, d, seed=0))
        svc.drain(timeout=120)
        epoch0, centers, r = svc.snapshot()
        svc.assign(centers[:1], timeout=60)      # populate the cache
        st0 = svc.stats

        # points sitting exactly on (and 1e-6 off) the live centers are
        # covered: the sketch must absorb them without publishing
        for _ in range(5):
            svc.submit_points(centers)
            svc.submit_points(centers + 1e-6)
        svc.drain(timeout=120)
        assert svc.snapshot()[0] == epoch0

        svc.assign(centers[:3], timeout=60)
        st1 = svc.stats
        assert st1["epochs"] == st0["epochs"]
        assert st1["cache_refreshes"] == st0["cache_refreshes"]


# ---------------------------------------------------------------------------
# recompile discipline: one signature set, forever
# ---------------------------------------------------------------------------

def _spy_bucketed(monkeypatch, seen):
    real = ops.assign_bucketed

    def spy(q, c, cmask, **kw):
        seen.append((q.shape, c.shape, np.asarray(cmask).shape,
                     kw.get("impl"), kw.get("chunk")))
        return real(q, c, cmask, **kw)

    monkeypatch.setattr(ops, "assign_bucketed", spy)


def test_one_signature_across_ragged_batches_and_epochs(monkeypatch):
    k, d = 8, 6
    seen = []
    _spy_bucketed(monkeypatch, seen)
    with KCenterService(k, d, min_bucket=16, center_bucket_min=16,
                        snapshot_history=True) as svc:
        svc.submit_points(_clustered(600, k, d, seed=0))
        svc.drain(timeout=120)
        rng = np.random.default_rng(0)
        svc.assign(rng.normal(size=(5, d)).astype(np.float32), timeout=60)
        warm = set(seen)
        assert len(warm) == 1             # one (query-bucket, center-bucket)

        # ragged sizes all inside the same 16-row bucket
        for b in (1, 3, 7, 12, 16):
            svc.assign(rng.normal(size=(b, d)).astype(np.float32),
                       timeout=60)
        assert set(seen) == warm

        # an epoch bump within the same center bucket: cache re-uploads,
        # signatures must not move
        st_before = svc.stats
        svc.submit_points(_clustered(200, k, d, seed=1) * 50.0)
        svc.drain(timeout=120)
        assert svc.stats["epochs"] > st_before["epochs"]
        svc.assign(rng.normal(size=(9, d)).astype(np.float32), timeout=60)
        assert set(seen) == warm
        assert svc.stats["bucket_growths"] == 1   # only the initial fill


def test_query_buckets_are_pow2_and_capped(monkeypatch):
    k, d = 4, 3
    seen = []
    _spy_bucketed(monkeypatch, seen)
    with KCenterService(k, d, min_bucket=4, max_batch=8) as svc:
        svc.submit_points(_clustered(300, k, d, seed=0))
        svc.drain(timeout=120)
        rng = np.random.default_rng(0)
        # 21 rows > max_batch: slices of 8, 8, 5 → buckets 8, 8, 8
        svc.assign(rng.normal(size=(21, d)).astype(np.float32), timeout=60)
        qrows = [s[0][0] for s in seen]
        assert qrows == [8, 8, 8]
        seen.clear()
        svc.assign(rng.normal(size=(3, d)).astype(np.float32), timeout=60)
        assert [s[0][0] for s in seen] == [4]     # pow2 floor bucket


# ---------------------------------------------------------------------------
# batching behavior
# ---------------------------------------------------------------------------

def test_concurrent_clients_coalesce_and_stay_correct():
    k, d = 8, 5
    n_clients = 16
    with KCenterService(k, d, batch_wait_s=0.05) as svc:
        svc.submit_points(_clustered(500, k, d, seed=0))
        svc.drain(timeout=120)
        _, centers, _ = svc.snapshot()
        rng = np.random.default_rng(0)
        qs = [rng.normal(size=(1 + i % 4, d)).astype(np.float32)
              for i in range(n_clients)]
        tickets = [svc.assign_async(q) for q in qs]
        for q, t in zip(qs, tickets):
            res = t.result(timeout=60)
            ri, rd = _offline(q, centers)
            assert np.array_equal(ri, res.idx)
            assert np.array_equal(rd, res.d2)
        st = svc.stats
        assert st["queries"] == n_clients
        assert st["batches"] < n_clients          # coalescing happened
        assert st["batched_rows"] == sum(q.shape[0] for q in qs)


def test_unbatched_mode_dispatches_each_request_alone():
    k, d = 4, 3
    with KCenterService(k, d, batching=False) as svc:
        svc.submit_points(_clustered(200, k, d, seed=0))
        svc.drain(timeout=120)
        rng = np.random.default_rng(0)
        for _ in range(4):
            svc.assign(rng.normal(size=(2, d)).astype(np.float32),
                       timeout=60)
        st = svc.stats
        assert st["batches"] == st["queries"] == 4


def test_ticket_timestamps_and_done():
    k, d = 4, 3
    with KCenterService(k, d) as svc:
        svc.submit_points(_clustered(200, k, d, seed=0))
        svc.drain(timeout=120)
        t = svc.assign_async(np.zeros((1, d), np.float32))
        assert isinstance(t, AssignTicket)
        t.result(timeout=60)
        assert t.done()
        assert t.t_done >= t.t_submit


# ---------------------------------------------------------------------------
# ingest surface
# ---------------------------------------------------------------------------

def test_point_source_ingest_matches_offline_fold():
    k, d = 8, 4
    pts = _clustered(700, k, d, seed=2)
    with KCenterService(k, d, ingest_block_rows=128) as svc:
        svc.submit_points(HostSource(pts))
        svc.drain(timeout=120)
        _, centers, r = svc.snapshot()
    ref = stream_update(stream_init(k, d), HostSource(pts), block_rows=128)
    ref_c, ref_r = stream_result(ref)
    assert r == ref_r
    assert np.array_equal(centers, ref_c)


def test_ingest_error_surfaces_on_drain():
    with KCenterService(4, 3) as svc:
        with pytest.raises(ValueError):
            svc.submit_points(np.zeros((5, 7), np.float32))  # wrong d
        svc.submit_points(np.zeros((5, 3), np.float32))
        svc.drain(timeout=120)


# ---------------------------------------------------------------------------
# lifecycle + validation
# ---------------------------------------------------------------------------

def test_assign_before_any_centers_fails():
    with KCenterService(4, 3) as svc:
        with pytest.raises(RuntimeError, match="no centers"):
            svc.assign(np.zeros((1, 3), np.float32), timeout=60)


def test_closed_service_rejects_work():
    svc = KCenterService(4, 3)
    svc.close()
    with pytest.raises(RuntimeError):
        svc.assign(np.zeros((1, 3), np.float32))
    with pytest.raises(RuntimeError):
        svc.submit_points(np.zeros((1, 3), np.float32))
    svc.close()                                    # idempotent


def test_query_validation():
    with KCenterService(4, 3) as svc:
        with pytest.raises(ValueError):
            svc.assign_async(np.zeros((2, 5), np.float32))   # wrong d
        with pytest.raises(ValueError):
            svc.assign_async(np.zeros((0, 3), np.float32))   # empty
        t = svc.assign_async(np.zeros(3, np.float32))        # (d,) promotes
        assert t.q.shape == (1, 3)
        svc.close()


# ---------------------------------------------------------------------------
# streaming insertion tail (core/streaming.py perf fix)
# ---------------------------------------------------------------------------

def test_stream_update_tail_validation():
    st = stream_init(4, 3)
    with pytest.raises(ValueError, match="tail"):
        stream_update(st, np.zeros((2, 3), np.float32), tail="gpu")


@pytest.mark.parametrize("tail", ["host", "device"])
def test_tail_invariants(tail):
    """Both tails keep the doubling invariants: ≤ k centers at rest,
    pairwise separation > r."""
    k, d = 6, 4
    rng = np.random.default_rng(0)
    pts = rng.normal(size=(800, d)).astype(np.float32)
    pts *= np.linspace(1.0, 40.0, 800, dtype=np.float32)[:, None]
    st = stream_init(k, d)
    for i in range(0, 800, 100):
        st = stream_update(st, pts[i:i + 100], tail=tail)
        assert st.count <= k + 1
    centers, r = stream_result(st)
    assert centers.shape[0] <= k
    if centers.shape[0] > 1 and r > 0:
        diff = centers[:, None, :] - centers[None, :, :]
        dist = np.sqrt((diff ** 2).sum(-1))
        np.fill_diagonal(dist, np.inf)
        assert dist.min() > r


def test_host_tail_matches_device_tail_on_separated_data():
    """On well-separated clustered data (decision margins ≫ 1 ulp) the two
    tails walk the identical doubling trajectory."""
    k, d = 8, 4
    pts = _clustered(2000, k, d, seed=5)
    st_h = stream_init(k, d)
    st_d = stream_init(k, d)
    for i in range(0, 2000, 250):
        st_h = stream_update(st_h, pts[i:i + 250], tail="host")
        st_d = stream_update(st_d, pts[i:i + 250], tail="device")
    assert st_h.count == st_d.count
    assert st_h.r == st_d.r
    assert np.array_equal(st_h.centers[:st_h.count],
                          st_d.centers[:st_d.count])


def test_host_tail_covers_every_streamed_point():
    """8-approx guarantee proxy: every point ends within 4r of a center."""
    k, d = 6, 3
    rng = np.random.default_rng(4)
    pts = rng.normal(size=(1500, d)).astype(np.float32)
    pts *= np.linspace(1.0, 30.0, 1500, dtype=np.float32)[:, None]
    st = stream_init(k, d)
    for i in range(0, 1500, 300):
        st = stream_update(st, pts[i:i + 300], tail="host")
    centers, r = stream_result(st)
    _, d2 = _offline(pts, centers)
    assert float(np.sqrt(d2).max()) <= 4.0 * r + 1e-4


# ---------------------------------------------------------------------------
# bootstrap: duplicate rows never leave r at 0 once k+1 distinct points
# have arrived (a doubling of r = 0 would never shrink the center set)
# ---------------------------------------------------------------------------

def _with_early_duplicates(k, d, n, seed, gap=0.0):
    """Points whose first k+1 rows hold duplicates — exact at ``gap`` 0,
    else ``gap`` apart at a norm where the expanded |x|²+|y|²−2x·y form
    rounds their distance to 0 — then more than k distinct points."""
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(n, d)).astype(np.float32)
    pts *= np.linspace(1.0, 20.0, n, dtype=np.float32)[:, None]
    pts += np.float32(100.0)
    for dup, orig in ((2, 0), (4, 0), (5, 3)):
        pts[dup] = pts[orig]
        pts[dup, 0] += np.float32(gap)
    return pts


@pytest.mark.time_limit(30)
@pytest.mark.parametrize("gap", [0.0, 0.03], ids=["exact", "near"])
@pytest.mark.parametrize("tail", ["host", "device"])
def test_duplicates_in_bootstrap_finish_with_positive_radius(tail, gap):
    k, d = 6, 4
    pts = _with_early_duplicates(k, d, 600, seed=1, gap=gap)
    cuts = [0, 3, 100, 200, 300, 400, 500, 600]   # bootstrap spans calls
    st = stream_init(k, d)
    for lo, hi in zip(cuts, cuts[1:]):
        st = stream_update(st, pts[lo:hi], tail=tail)
        assert st.count <= k + 1
    centers, r = stream_result(st)
    assert r > 0
    assert 1 < centers.shape[0] <= k
    diff = centers[:, None, :] - centers[None, :, :]
    dist = np.sqrt((diff ** 2).sum(-1))
    np.fill_diagonal(dist, np.inf)
    assert dist.min() > r


@pytest.mark.time_limit(30)
@pytest.mark.parametrize("tail", ["host", "device"])
def test_identical_rows_keep_one_center_at_zero_radius(tail):
    k, d = 4, 3
    pts = np.full((5000, d), 7.5, np.float32)
    st = stream_update(stream_init(k, d), pts, tail=tail)
    st = stream_update(st, pts[:10], tail=tail)
    centers, r = stream_result(st)
    assert r == 0.0
    np.testing.assert_array_equal(centers, pts[:1])


@pytest.mark.time_limit(30)
def test_service_ingest_of_duplicate_rows_drains():
    k, d = 6, 4
    pts = _with_early_duplicates(k, d, 400, seed=2, gap=0.03)
    with KCenterService(k, d) as svc:
        svc.submit_points(pts)
        svc.drain(timeout=20)
        _, centers, r = svc.snapshot()
    assert r > 0
    assert 1 < centers.shape[0] <= k
