"""Continuous-batching serve engine: bucket policy edge cases, window
flush, pad-to-bucket parity, replica fairness, and plan/prepared-cache
dedupe across engines (the serving lifecycle from the ROADMAP)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.conv import (
    BucketedNetworkPlan, NetworkConv, clear_plan_cache,
    clear_prepared_cache, plan_cache_info, plan_network,
    prepared_cache_info,
)
from repro.launch.batcher import (
    BucketPolicy, RequestTooLarge, ServeEngine, TraceRequest, _percentile,
    run_trace, synthetic_trace,
)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _layers(batch, image=8):
    return [
        NetworkConv("s1", (batch, 2, image, image), (4, 2, 3, 3),
                    padding=1),
        NetworkConv("s2", (batch, 4, image, image), (4, 4, 3, 3),
                    padding=1),
    ]


def _params():
    return {"s1": _rand((4, 2, 3, 3), 1), "s2": _rand((4, 4, 3, 3), 2)}


def _engine(**kw):
    kw.setdefault("policy", BucketPolicy(max_batch=4))
    kw.setdefault("backend", "fft-xla")
    return ServeEngine(_layers, _params(), **kw)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# --------------------------------------------------------------------------
# Bucket policy
# --------------------------------------------------------------------------

def test_batch_buckets_powers_of_two_max_included():
    assert BucketPolicy(max_batch=8).batch_buckets() == (1, 2, 4, 8)
    # non-power max is still its own bucket
    assert BucketPolicy(max_batch=6).batch_buckets() == (1, 2, 4, 6)
    assert BucketPolicy(max_batch=1).batch_buckets() == (1,)
    assert BucketPolicy(max_batch=8, min_batch=2).batch_buckets() == \
        (2, 4, 8)


def test_bucket_for_rounds_up():
    p = BucketPolicy(max_batch=8)
    assert [p.bucket_for(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]


def test_bucket_for_rejects_oversize_with_clear_error():
    p = BucketPolicy(max_batch=4)
    with pytest.raises(RequestTooLarge, match="max_batch=4"):
        p.bucket_for(5)
    with pytest.raises(ValueError, match=">= 1"):
        p.bucket_for(0)


def test_bucket_policy_validates_bounds_and_image_sizes():
    with pytest.raises(ValueError):
        BucketPolicy(max_batch=0)
    with pytest.raises(ValueError):
        BucketPolicy(max_batch=2, min_batch=4)
    p = BucketPolicy(max_batch=4, image_sizes=(8, 16))
    assert p.bucket_for(2, image=8) == 2
    with pytest.raises(RequestTooLarge, match="image size"):
        p.bucket_for(2, image=32)


def test_percentile_nearest_rank():
    vals = [float(i) for i in range(1, 101)]
    assert _percentile(vals, 50) == pytest.approx(50.0, abs=1.0)
    assert _percentile(vals, 99) == pytest.approx(99.0, abs=1.0)
    assert _percentile([7.0], 99) == 7.0
    assert np.isnan(_percentile([], 50))


# --------------------------------------------------------------------------
# Engine edge cases
# --------------------------------------------------------------------------

def test_submit_oversize_rejected_and_counted():
    eng = _engine()
    with pytest.raises(RequestTooLarge):
        eng.submit(jnp.zeros((5, 2, 8, 8), jnp.float32))
    rep = eng.report()
    assert rep["n_rejected"] == 1 and rep["n_requests"] == 0


def test_drain_empty_queue_is_noop():
    eng = _engine()
    assert eng.drain() == 0
    assert eng.drain(force=True) == 0
    assert eng.queue_depth == 0


def test_window_holds_partial_batch_until_timeout():
    clock = FakeClock()
    eng = _engine(window_s=1.0, clock=clock)
    eng.submit(_rand((1, 2, 8, 8)))
    assert eng.drain() == 0 and eng.queue_depth == 1   # window open
    clock.t = 0.5
    assert eng.drain() == 0                            # still open
    clock.t = 1.5
    assert eng.drain() == 1 and eng.queue_depth == 0   # timed out: flush


def test_full_bucket_launches_inside_window():
    clock = FakeClock()
    eng = _engine(window_s=60.0, clock=clock)
    for i in range(4):
        eng.submit(_rand((1, 2, 8, 8), seed=i))
    assert eng.drain() == 1                 # max_batch rows: no waiting
    assert eng.report()["buckets"]["b4"]["occupancy"] == 1.0


def test_force_drain_flushes_open_window():
    clock = FakeClock()
    eng = _engine(window_s=60.0, clock=clock)
    eng.submit(_rand((3, 2, 8, 8)))
    assert eng.drain() == 0
    assert eng.drain(force=True) == 1       # end-of-trace flush
    assert "b4" in eng.report()["buckets"]  # 3 rows pad to bucket 4


def test_pad_to_bucket_parity_with_unpadded_execution():
    """A padded+sliced bucketed result must equal running the request
    through a network planned for its exact (unpadded) shape."""
    eng = _engine()
    x = _rand((3, 2, 8, 8), seed=7)
    rid = eng.submit(x)
    eng.drain(force=True)                   # 3 rows -> bucket 4 (padded)
    y = eng.results[rid]
    assert y.shape[0] == 3

    net = plan_network(_layers(3), backend="fft-xla")
    prepared = net.prepare(_params(), weights_version=0)
    h = x
    for name in net.layer_names:
        h = prepared[name](h)
    np.testing.assert_allclose(np.asarray(y), np.asarray(h),
                               rtol=1e-4, atol=1e-4)


def test_fifo_coalescing_packs_same_image_requests():
    eng = _engine()
    rids = [eng.submit(_rand((2, 2, 8, 8), seed=i)) for i in range(2)]
    assert eng.drain() == 1                 # 2+2 rows -> ONE b4 batch
    rep = eng.report()
    assert rep["buckets"]["b4"]["n_batches"] == 1
    assert rep["buckets"]["b4"]["n_requests"] == 2
    assert rep["occupancy"] == 1.0
    assert all(eng.results[r].shape[0] == 2 for r in rids)


def test_pad_max_baseline_never_coalesces():
    eng = _engine(mode="pad-max")
    for i in range(3):
        eng.submit(_rand((1, 2, 8, 8), seed=i))
    assert eng.drain(force=True) == 3       # one request per batch
    rep = eng.report()
    assert rep["buckets"]["b4"]["n_batches"] == 3
    assert rep["occupancy"] == pytest.approx(3 / 12)


def test_replan_baseline_pays_plan_misses_on_hot_path():
    clear_plan_cache()
    eng = _engine(mode="replan")
    for b in (1, 3, 1):
        eng.submit(_rand((b, 2, 8, 8), seed=b))
    eng.drain(force=True)
    rep = eng.report()
    # two distinct shapes planned on the hot path; the repeat hits
    assert rep["plan_cache_misses_after_warmup"] > 0


def test_bucketed_zero_plan_misses_after_warmup():
    eng = _engine()
    trace = synthetic_trace(n_requests=12, max_batch=4, rate_rps=1.0,
                            seed=0)
    rep = run_trace(eng, trace, realtime=False,
                    make_input=lambda b, img: _rand((b, 2, 8, 8), b))
    assert rep["plan_cache_misses_after_warmup"] == 0
    assert rep["n_requests"] == 12


def test_replica_round_robin_fairness():
    eng = _engine(policy=BucketPolicy(max_batch=2), replicas=2)
    for i in range(8):
        eng.submit(_rand((2, 2, 8, 8), seed=i))
    eng.drain(force=True)
    rep = eng.report()
    assert rep["replica_batches"] == [4, 4]
    assert rep["n_requests"] == 8


def test_prepared_cache_dedupe_across_engine_builds():
    """A second engine over the same params/policy re-plans and
    re-prepares entirely out of the shared caches: zero new plan misses,
    one prepared-cache hit per (bucket, layer)."""
    clear_plan_cache()
    clear_prepared_cache()
    params = _params()
    policy = BucketPolicy(max_batch=4)
    ServeEngine(_layers, params, policy=policy, backend="fft-xla")
    plan_misses = plan_cache_info().misses
    hits_before = prepared_cache_info().hits

    eng2 = ServeEngine(_layers, params, policy=policy, backend="fft-xla")
    assert plan_cache_info().misses == plan_misses
    n_buckets = len(policy.batch_buckets())
    assert prepared_cache_info().hits >= hits_before + 2 * n_buckets
    assert eng2.report()["plan_cache_misses_after_warmup"] == 0


def test_update_weights_invalidates_once_per_bucket():
    eng = _engine(policy=BucketPolicy(max_batch=2))
    x = _rand((1, 2, 8, 8), seed=3)
    rid = eng.submit(x)
    eng.drain(force=True)
    y_old = np.asarray(eng.results[rid])

    new = {k: v * 2.0 for k, v in _params().items()}
    eng.update_weights(new, weights_version=1)
    rid2 = eng.submit(x)
    eng.drain(force=True)
    y_new = np.asarray(eng.results[rid2])
    assert not np.allclose(y_old, y_new)    # new weights took effect
    assert eng.report()["plan_cache_misses_after_warmup"] == 0


# --------------------------------------------------------------------------
# Trace + bench rows
# --------------------------------------------------------------------------

def test_synthetic_trace_is_deterministic_and_in_range():
    a = synthetic_trace(n_requests=16, max_batch=8, rate_rps=5.0, seed=3)
    b = synthetic_trace(n_requests=16, max_batch=8, rate_rps=5.0, seed=3)
    assert a == b and len(a) == 16
    assert all(1 <= tr.batch <= 8 for tr in a)
    assert all(a[i].t < a[i + 1].t for i in range(len(a) - 1))
    c = synthetic_trace(n_requests=16, max_batch=8, rate_rps=5.0, seed=4)
    assert c != a


def test_realtime_trace_replay_sleeps_to_offsets():
    eng = _engine(policy=BucketPolicy(max_batch=2))
    slept = []
    trace = (TraceRequest(t=0.05, batch=1), TraceRequest(t=0.10, batch=2))
    rep = run_trace(eng, trace, realtime=True, sleep=slept.append,
                    make_input=lambda b, img: _rand((b, 2, 8, 8), b))
    assert rep["n_requests"] == 2
    assert len(slept) >= 1 and all(dt > 0 for dt in slept)


def test_bench_rows_schema_valid_with_percentiles():
    from benchmarks.bench_schema import normalize
    eng = _engine()
    trace = synthetic_trace(n_requests=8, max_batch=4, rate_rps=1.0,
                            seed=1)
    run_trace(eng, trace, realtime=False,
              make_input=lambda b, img: _rand((b, 2, 8, 8), b))
    rows = normalize(eng.bench_rows())
    labels = {n.split("/")[1] for n in rows}
    assert labels <= {"b1", "b2", "b4"} and rows
    for name, entry in rows.items():
        metric = name.split("/")[2]
        assert metric in ("p50", "p99", "occupancy")
        if metric != "occupancy":
            assert entry["percentiles"]["p99"] >= \
                entry["percentiles"]["p50"]
        assert entry["config"]["mode"] == "bucketed"


# --------------------------------------------------------------------------
# netplan bucket helpers
# --------------------------------------------------------------------------

def test_plan_network_buckets_dedupe_report():
    nets = plan_network(_layers, buckets=(1, 2, 4), backend="fft-xla")
    assert isinstance(nets, BucketedNetworkPlan)
    assert tuple(nets) == (1, 2, 4)
    rep = nets.report()
    assert rep["n_buckets"] == 3
    assert rep["n_layer_plans"] == 6
    # distinct batch -> distinct plans; within a bucket s2's geometry is
    # unique too, so no cross-bucket dedupe in this net
    assert rep["n_distinct_plans"] == 6
    with pytest.raises(ValueError, match="duplicate"):
        plan_network(_layers, buckets=(2, 2), backend="fft-xla")
    # a callable layer factory needs buckets=
    with pytest.raises(TypeError, match="buckets"):
        plan_network(_layers, backend="fft-xla")
