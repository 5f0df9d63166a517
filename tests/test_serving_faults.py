"""Fault-tolerant serving tests: every recovery path in ``CNNServer``
driven deterministically through the ``serving.faults`` harness — no
real sleeps, no wall-clock dependence.

Covers: admission control (queue-full rejection, non-finite frames,
unmeetable deadlines), deadline expiry ordering under an injectable
clock, retry/backoff schedules, poison-batch bisection isolating
exactly one request (batchmates byte-identical to a fault-free run),
non-finite output detection, circuit-breaker trip/shed/half-open/reset,
degradation-ladder hysteresis with ``CNNEngine.switch_verified``
pre-validation, and the drained-vs-wedged contract of
``run_until_drained``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.findings import Finding
from repro.core.engine import CNNEngine
from repro.core.methods import Method
from repro.core.netdefs import NETWORKS
from repro.serving.cnn import (CNNServer, FailedResult, ImageRequest,
                               ImageResult, NonFiniteInputError,
                               ServerWedgedError, ShedResult,
                               SupervisorConfig)
from repro.serving.degrade import DegradeController, Rung, default_ladder
from repro.serving.faults import (FaultInjector, FaultScript,
                                  PersistentEngineFault,
                                  TransientEngineFault)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class FakeSleep:
    def __init__(self):
        self.delays = []

    def __call__(self, s):
        self.delays.append(s)


@pytest.fixture(scope="module")
def lenet():
    net = NETWORKS["lenet5"]()
    eng = CNNEngine(net, method=Method.ADVANCED_SIMD_8)
    params = eng.init(jax.random.PRNGKey(0))
    imgs = np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (16, *net.input_shape), jnp.float32))
    return net, eng, params, imgs


def _server(eng, params, **kw):
    kw.setdefault("clock", FakeClock())
    kw.setdefault("sleep", FakeSleep())
    return CNNServer(eng, params, **kw)


def _submit(server, imgs, rids, **req_kw):
    out = []
    for r in rids:
        out.append(server.submit(
            ImageRequest(rid=r, image=imgs[r % len(imgs)], **req_kw)))
    return out


# ---------------------------------------------------------------------------
# fault harness basics
# ---------------------------------------------------------------------------


def test_empty_script_injector_is_transparent(lenet):
    """A wired-but-empty FaultScript must not change a single bit of
    the serving output."""
    net, eng, params, imgs = lenet
    plain = _server(eng, params, max_batch=4, max_delay_s=0.0)
    _submit(plain, imgs, range(4))
    plain.run_until_drained()
    inj = FaultInjector(FaultScript())
    faulted = _server(eng, params, max_batch=4, max_delay_s=0.0,
                      fault_injector=inj)
    _submit(faulted, imgs, range(4))
    faulted.run_until_drained()
    for r in range(4):
        assert faulted.done[r].top_probs == plain.done[r].top_probs
        assert faulted.done[r].top_indices == plain.done[r].top_indices
    assert inj.calls == 1 and inj.events == []


def test_injected_faults_raise_typed(lenet):
    net, eng, params, imgs = lenet
    inj = FaultInjector(FaultScript(transient_calls={0},
                                    persistent_calls={1}))
    x = np.zeros((1, *net.input_shape), np.float32)
    with pytest.raises(TransientEngineFault):
        inj(lambda a: a, x, [0])
    with pytest.raises(PersistentEngineFault):
        inj(lambda a: a, x, [0])
    assert [e["kind"] for e in inj.events] == ["transient", "persistent"]


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_queue_full_rejection(lenet):
    net, eng, params, imgs = lenet
    srv = _server(eng, params, max_batch=4, max_delay_s=10.0, max_queue=2)
    assert srv.submit(ImageRequest(rid=0, image=imgs[0])) is None
    assert srv.submit(ImageRequest(rid=1, image=imgs[1])) is None
    shed = srv.submit(ImageRequest(rid=2, image=imgs[2]))
    assert isinstance(shed, ShedResult) and shed.reason == "queue_full"
    assert not shed.ok
    assert srv.done[2] is shed          # recorded, never silently dropped
    assert srv.pending() == 2
    s = srv.stats()
    assert s["rejected"] == 1 and s["shed"] == 1


def test_non_finite_frame_rejected_at_admission(lenet):
    net, eng, params, imgs = lenet
    srv = _server(eng, params)
    bad = imgs[0].copy()
    bad[0, 3, 3] = np.nan
    with pytest.raises(NonFiniteInputError, match="non-finite"):
        srv.submit(ImageRequest(rid=0, image=bad))
    bad[0, 3, 3] = np.inf
    with pytest.raises(ValueError):     # NonFiniteInputError is a ValueError
        srv.submit(ImageRequest(rid=0, image=bad))
    assert srv.pending() == 0


def test_unmeetable_deadline_shed_at_admission(lenet):
    """A deadline below the measured service-time estimate (EWMA over
    executed batches) is shed up front — the request could not be
    served in time even if a batch flushed immediately."""
    net, eng, params, imgs = lenet
    clock = FakeClock()
    inj = FaultInjector(FaultScript(latency_spikes={0: 1.0}),
                        advance=clock.advance)
    srv = _server(eng, params, max_batch=1, max_delay_s=0.0, clock=clock,
                  fault_injector=inj)
    _submit(srv, imgs, [0])
    srv.run_until_drained()             # service estimate is now ~1.0s
    assert srv.health()["service_estimate_s"] == pytest.approx(1.0)
    shed = srv.submit(ImageRequest(rid=1, image=imgs[1], deadline_s=0.5))
    assert isinstance(shed, ShedResult)
    assert shed.reason == "admission_deadline"
    # a zero/negative deadline is unmeetable regardless of any estimate
    shed0 = srv.submit(ImageRequest(rid=2, image=imgs[2], deadline_s=0.0))
    assert shed0.reason == "admission_deadline"
    # a comfortable deadline is admitted
    assert srv.submit(
        ImageRequest(rid=3, image=imgs[3], deadline_s=5.0)) is None


def test_deadline_expiry_ordering_under_injectable_clock(lenet):
    """Queued requests expire exactly when the clock passes each one's
    absolute deadline, in deadline order, as typed sheds — survivors
    keep FIFO order and are served."""
    net, eng, params, imgs = lenet
    clock = FakeClock()
    srv = _server(eng, params, max_batch=8, max_delay_s=100.0, clock=clock)
    srv.submit(ImageRequest(rid=0, image=imgs[0], deadline_s=1.0))
    srv.submit(ImageRequest(rid=1, image=imgs[1], deadline_s=3.0))
    srv.submit(ImageRequest(rid=2, image=imgs[2], deadline_s=0.5))
    clock.t = 0.6
    out = srv.step()                    # no flush: only the expiry runs
    assert [r.rid for r in out] == [2]
    assert isinstance(out[0], ShedResult)
    assert out[0].reason == "deadline_expired"
    assert out[0].waited_s == pytest.approx(0.6)
    clock.t = 1.2
    out = srv.step()
    assert [r.rid for r in out] == [0]
    assert srv.pending() == 1
    (served,) = srv.step(force=True)    # the survivor is served
    assert isinstance(served, ImageResult) and served.rid == 1
    s = srv.stats()
    assert s["expired"] == 2 and s["shed"] == 2 and s["served"] == 1


def test_default_deadline_applies_to_requests_without_one(lenet):
    net, eng, params, imgs = lenet
    clock = FakeClock()
    srv = _server(eng, params, max_batch=8, max_delay_s=100.0, clock=clock,
                  default_deadline_s=1.0)
    srv.submit(ImageRequest(rid=0, image=imgs[0]))                 # default
    srv.submit(ImageRequest(rid=1, image=imgs[1], deadline_s=9.0))  # override
    clock.t = 2.0
    out = srv.step()
    assert [r.rid for r in out] == [0]
    assert out[0].reason == "deadline_expired"


# ---------------------------------------------------------------------------
# supervised execution: retry, bisection, output validation
# ---------------------------------------------------------------------------


def test_retry_backoff_schedule(lenet):
    """Two scripted transient faults retry with capped exponential
    backoff through the injectable sleep, then succeed — the batch is
    served, nothing fails."""
    net, eng, params, imgs = lenet
    sleep = FakeSleep()
    inj = FaultInjector(FaultScript(transient_calls={0, 1}))
    srv = _server(eng, params, max_batch=2, max_delay_s=0.0, sleep=sleep,
                  fault_injector=inj,
                  supervisor=SupervisorConfig(max_retries=2,
                                              backoff_base_s=0.01,
                                              backoff_cap_s=0.25))
    _submit(srv, imgs, range(2))
    srv.run_until_drained()
    assert all(isinstance(srv.done[r], ImageResult) for r in range(2))
    assert sleep.delays == [0.01, 0.02]       # base * 2**attempt
    s = srv.stats()
    assert s["retried"] == 2 and s["failed"] == 0
    assert inj.calls == 3                     # 2 faulted attempts + success


def test_backoff_is_capped(lenet):
    net, eng, params, imgs = lenet
    sleep = FakeSleep()
    inj = FaultInjector(FaultScript(transient_calls={0, 1, 2, 3}))
    srv = _server(eng, params, max_batch=1, max_delay_s=0.0, sleep=sleep,
                  fault_injector=inj,
                  supervisor=SupervisorConfig(max_retries=4,
                                              backoff_base_s=0.1,
                                              backoff_cap_s=0.25))
    _submit(srv, imgs, [0])
    srv.run_until_drained()
    assert sleep.delays == [0.1, 0.2, 0.25, 0.25]   # capped, not 0.4/0.8
    assert isinstance(srv.done[0], ImageResult)


def test_transient_exhaustion_falls_back_to_bisection(lenet):
    """When retries are exhausted the batch bisects; sub-batches get a
    fresh retry budget, so a fault that clears mid-bisection still
    serves every request."""
    net, eng, params, imgs = lenet
    sleep = FakeSleep()
    inj = FaultInjector(FaultScript(transient_calls={0, 1, 2}))
    srv = _server(eng, params, max_batch=2, max_delay_s=0.0, sleep=sleep,
                  fault_injector=inj,
                  supervisor=SupervisorConfig(max_retries=1,
                                              backoff_base_s=0.01))
    _submit(srv, imgs, range(2))
    srv.run_until_drained()
    # calls: 0 fail, 1 fail (budget out) -> bisect: 2 fail, 3 ok; 4 ok
    assert all(isinstance(srv.done[r], ImageResult) for r in range(2))
    s = srv.stats()
    assert s["retried"] == 2 and s["bisections"] == 1 and s["failed"] == 0


def test_poison_batch_bisection_isolates_exactly_one(lenet):
    """The acceptance scenario: one poison request in a batch of 4
    yields ONE typed FailedResult; every batchmate's result is
    byte-identical to a fault-free run (bisection sub-batches keep the
    parent's pow2 bucket, so the same compiled executable serves them)."""
    net, eng, params, imgs = lenet
    clean = _server(eng, params, max_batch=4, max_delay_s=0.0)
    _submit(clean, imgs, range(4), top_k=4)
    clean.run_until_drained()

    inj = FaultInjector(FaultScript(poison_rids={2}))
    srv = _server(eng, params, max_batch=4, max_delay_s=0.0,
                  fault_injector=inj,
                  supervisor=SupervisorConfig(max_retries=2))
    _submit(srv, imgs, range(4), top_k=4)
    srv.run_until_drained()

    failed = [r for r in srv.done.values() if isinstance(r, FailedResult)]
    assert [f.rid for f in failed] == [2]
    assert failed[0].error == "engine_fault"
    assert "PersistentEngineFault" in failed[0].detail
    for r in (0, 1, 3):
        res = srv.done[r]
        assert isinstance(res, ImageResult)
        assert res.top_probs == clean.done[r].top_probs      # byte-identical
        assert res.top_indices == clean.done[r].top_indices
        assert res.bucket == 4          # bisection kept the parent bucket
    s = srv.stats()
    assert s["failed"] == 1 and s["served"] == 3 and s["bisections"] >= 1
    # persistent faults never consumed the retry budget
    assert s["retried"] == 0


def test_non_finite_output_row_becomes_typed_failure(lenet):
    """A corrupted output row (NaN) is detected and converted into a
    per-request failure — batchmates still get finite, correct top-k."""
    net, eng, params, imgs = lenet
    clean = _server(eng, params, max_batch=4, max_delay_s=0.0)
    _submit(clean, imgs, range(4))
    clean.run_until_drained()

    inj = FaultInjector(FaultScript(corrupt_rids={1}))
    srv = _server(eng, params, max_batch=4, max_delay_s=0.0,
                  fault_injector=inj)
    _submit(srv, imgs, range(4))
    srv.run_until_drained()
    res = srv.done[1]
    assert isinstance(res, FailedResult)
    assert res.error == "non_finite_output"
    for r in (0, 2, 3):
        assert srv.done[r].top_probs == clean.done[r].top_probs
        assert all(np.isfinite(srv.done[r].top_probs))
    assert srv.stats()["failed"] == 1


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_trips_sheds_and_resets(lenet):
    net, eng, params, imgs = lenet
    clock = FakeClock()
    # calls 0..5: two fully-failing steps (batch + 2 bisected singles
    # each); call 6+ clean so the half-open probe succeeds
    inj = FaultInjector(FaultScript(persistent_calls=frozenset(range(6))))
    srv = _server(eng, params, max_batch=2, max_delay_s=0.0, clock=clock,
                  fault_injector=inj,
                  supervisor=SupervisorConfig(breaker_threshold=2,
                                              breaker_reset_s=10.0))
    _submit(srv, imgs, [0, 1])
    srv.step(force=True)
    assert srv.health()["breaker"] == "closed"
    assert srv.health()["consecutive_failures"] == 1
    _submit(srv, imgs, [2, 3])
    srv.step(force=True)                     # second failing step: trip
    h = srv.health()
    assert h["breaker"] == "open" and h["state"] == "unhealthy"
    assert srv.stats()["breaker_trips"] == 1
    # open breaker sheds at admission and serves nothing
    shed = srv.submit(ImageRequest(rid=4, image=imgs[4]))
    assert isinstance(shed, ShedResult) and shed.reason == "breaker_open"
    assert srv.step(force=True) == []
    # after the reset window: half-open probe, success closes
    clock.t = 11.0
    assert srv.submit(ImageRequest(rid=5, image=imgs[5])) is None
    (res,) = srv.step(force=True)
    assert isinstance(res, ImageResult) and res.rid == 5
    h = srv.health()
    assert h["breaker"] == "closed" and h["state"] == "healthy"
    assert h["consecutive_failures"] == 0


def test_breaker_reopens_on_failed_probe(lenet):
    net, eng, params, imgs = lenet
    clock = FakeClock()
    inj = FaultInjector(FaultScript(persistent_calls=frozenset(range(9))))
    srv = _server(eng, params, max_batch=1, max_delay_s=0.0, clock=clock,
                  fault_injector=inj,
                  supervisor=SupervisorConfig(breaker_threshold=1,
                                              breaker_reset_s=5.0))
    _submit(srv, imgs, [0])
    srv.step(force=True)                     # trips immediately
    assert srv.health()["breaker"] == "open"
    clock.t = 6.0
    assert srv.submit(ImageRequest(rid=1, image=imgs[1])) is None
    srv.step(force=True)                     # half-open probe fails
    assert srv.health()["breaker"] == "open"
    assert srv.stats()["breaker_trips"] == 2


def test_run_until_drained_raises_when_wedged(lenet):
    """A wedged queue (breaker open, huge reset) must raise — not
    silently return with requests still pending."""
    net, eng, params, imgs = lenet
    inj = FaultInjector(FaultScript(persistent_calls=frozenset(range(3))))
    srv = _server(eng, params, max_batch=2, max_delay_s=0.0,
                  fault_injector=inj,
                  supervisor=SupervisorConfig(breaker_threshold=1,
                                              breaker_reset_s=1e9))
    _submit(srv, imgs, range(4))
    with pytest.raises(ServerWedgedError, match="not drained") as ei:
        srv.run_until_drained(max_steps=5)
    assert ei.value.report["pending"] == 2
    assert ei.value.report["pending_rids"] == [2, 3]
    assert ei.value.report["health"]["breaker"] == "open"
    assert srv.pending() == 2


def test_stats_throughput_zero_not_inf(lenet):
    """Under a frozen clock busy_s is 0 — the time numbers read 0.0,
    never inf or nan, and no rate over busy time is reported."""
    net, eng, params, imgs = lenet
    srv = _server(eng, params, max_batch=2, max_delay_s=0.0)
    _submit(srv, imgs, range(2))
    srv.run_until_drained()
    s = srv.stats()
    assert s["served"] == 2 and s["busy_s"] == 0.0
    assert s["p50_queue_wait_us"] == s["p95_queue_wait_us"] == 0.0
    assert s["p95_service_us"] == 0.0
    assert "throughput_rps" not in s


def test_queue_wait_and_service_time_under_the_injected_clock(lenet):
    """Queue wait runs from submit to the start of the batch's service,
    service from that start to the batch's end: their sum is each
    request's latency."""
    net, eng, params, imgs = lenet
    clock = FakeClock()
    inj = FaultInjector(FaultScript(latency_spikes={0: 0.5, 1: 0.25}),
                        advance=clock.advance)
    srv = _server(eng, params, max_batch=2, max_delay_s=10.0, clock=clock,
                  fault_injector=inj)
    _submit(srv, imgs, [0])                  # t = 0
    clock.t = 1.0
    _submit(srv, imgs, [1])                  # t = 1; the batch is full
    clock.t = 3.0
    srv.step()                               # service 3.0 -> 3.5
    _submit(srv, imgs, [2])                  # t = 3.5
    clock.t = 4.0
    srv.step(force=True)                     # service 4.0 -> 4.25
    s = srv.stats()
    waits = [3.0, 2.0, 0.5]
    assert s["batches"] == 2 and s["served"] == 3
    assert s["p50_queue_wait_us"] == pytest.approx(
        np.percentile(waits, 50) * 1e6)
    assert s["p95_queue_wait_us"] == pytest.approx(
        np.percentile(waits, 95) * 1e6)
    assert s["p95_service_us"] == pytest.approx(
        np.percentile([0.5, 0.25], 95) * 1e6)
    assert s["busy_s"] == pytest.approx(0.75)
    lat = sorted(srv.done[r].latency_s for r in range(3))
    assert lat == pytest.approx(sorted([3.5, 2.5, 0.75]))
    srv.reset_stats()
    assert "p95_queue_wait_us" not in srv.stats()


def test_every_served_batch_is_a_host_span_under_the_profiler(lenet,
                                                               tmp_path):
    """The server's and the engine's spans reach the profiler's host
    plane, nested as documented, and none is a benchmark span."""
    from jax.profiler import ProfileData

    net, eng, params, imgs = lenet
    srv = _server(eng, params, max_batch=4, max_delay_s=0.0)
    _submit(srv, imgs, range(3))             # a ragged batch: padded
    srv.run_until_drained()                  # warm, off the trace
    _submit(srv, imgs, range(10, 13))
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.step()
        np.asarray(eng.forward_batched(params, jnp.asarray(imgs[:3])))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    spans = {}
    for name, s, e, stats in events:
        if name.startswith("cnn."):
            spans.setdefault(name, []).append((s, e, stats))
    assert set(spans) == {
        "cnn.server.batch", "cnn.server.stack", "cnn.server.forward",
        "cnn.server.fetch", "cnn.server.topk", "cnn.forward_batched",
        "cnn.pad"}
    assert not [n for n, *_ in events if n.startswith("bench.")]
    ((b0, b1, batch),) = spans["cnn.server.batch"]
    assert batch == {"seq": 1, "size": 3}
    for child in ("cnn.server.stack", "cnn.server.forward",
                  "cnn.server.fetch", "cnn.server.topk"):
        ((s, e, _),) = spans[child]
        assert b0 <= s <= e <= b1, child
    # the server pads to the bucket itself; the direct call pads inside
    # forward_batched
    calls = sorted(spans["cnn.forward_batched"])
    assert [c[2] for c in calls] == [{"n": 4, "bucket": 4},
                                     {"n": 3, "bucket": 4}]
    ((p0, p1, _),) = spans["cnn.pad"]
    assert calls[1][0] <= p0 <= p1 <= calls[1][1]


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------


def test_default_ladder_walks_down_to_unfused_floor():
    ladder = default_ladder(Method.ADVANCED_SIMD_8, fuse=True)
    assert ladder == (Rung(Method.ADVANCED_SIMD_8, True),
                      Rung(Method.ADVANCED_SIMD_4, True),
                      Rung(Method.BASIC_SIMD, True),
                      Rung(Method.BASIC_SIMD, False))
    # starting unfused, the basic_simd floor is not duplicated
    assert default_ladder(Method.BASIC_SIMD, fuse=False) == (
        Rung(Method.BASIC_SIMD, False),)


def test_controller_hysteresis_and_cooldown():
    ctl = DegradeController(default_ladder(), queue_high=4, degrade_after=3,
                            recover_after=2, cooldown=2)
    # pressure must be SUSTAINED: 2 hot observations + 1 calm -> nothing
    assert ctl.observe(queue_depth=9) is None
    assert ctl.observe(queue_depth=9) is None
    assert ctl.observe(queue_depth=0) is None
    assert ctl.observe(queue_depth=9) is None
    assert ctl.observe(queue_depth=9) is None
    assert ctl.observe(queue_depth=9) == "down"
    ctl.commit(1)
    # cooldown dead-band: pressure keeps accumulating but cannot move
    assert ctl.observe(queue_depth=9) is None
    assert ctl.observe(queue_depth=9) is None
    assert ctl.observe(queue_depth=9) == "down"   # cooldown elapsed
    ctl.commit(2)
    # recovery needs its own sustained calm streak
    assert ctl.observe(queue_depth=0) is None     # cooldown
    assert ctl.observe(queue_depth=0) is None     # cooldown
    assert ctl.observe(queue_depth=0) == "up"     # calm streak >= 2
    ctl.commit(1)
    assert ctl.rung == 1 and ctl.moves == [1, 2, 1]


def test_controller_p95_slo_drift_is_pressure():
    ctl = DegradeController(default_ladder(), queue_high=100,
                            p95_slo_s=0.010, degrade_after=2, cooldown=0)
    assert ctl.observe(queue_depth=0, p95_s=0.030) is None
    assert ctl.observe(queue_depth=0, p95_s=0.030) == "down"
    # no p95 sample and an empty queue is calm
    assert ctl.pressured(queue_depth=0, p95_s=None) is False


def test_degradation_and_recovery_integration(lenet):
    """Sustained queue pressure walks the server down one verified rung
    (the engine's method really switches); sustained calm walks it back
    up — counters and health track both."""
    net, _, params, imgs = lenet
    eng = CNNEngine(net, method=Method.ADVANCED_SIMD_8)
    ladder = (Rung(Method.ADVANCED_SIMD_8, True),
              Rung(Method.ADVANCED_SIMD_4, True))
    ctl = DegradeController(ladder, queue_high=2, degrade_after=2,
                            recover_after=3, cooldown=0)
    srv = _server(eng, params, max_batch=2, max_delay_s=0.0, degrade=ctl)
    _submit(srv, imgs, range(8))
    srv.step(force=True)                     # pending 6 > 2: hot 1
    assert eng.method == Method.ADVANCED_SIMD_8
    srv.step(force=True)                     # pending 4 > 2: hot 2 -> down
    assert eng.method == Method.ADVANCED_SIMD_4      # verified switch stuck
    assert ctl.rung == 1
    assert srv.health()["state"] == "degraded"
    assert srv.stats()["degraded"] == 1
    # the committed rung was pre-validated: the live plan verifies clean
    assert not any(f.severity == "error" for f in eng.verify())
    srv.run_until_drained()
    # three calm observations (queue empty) walk it back up
    srv.step()
    srv.step()
    assert eng.method == Method.ADVANCED_SIMD_8
    assert ctl.rung == 0 and srv.stats()["recovered"] == 1
    assert srv.health()["state"] == "healthy"
    # every request was served despite the mid-stream replan
    assert all(isinstance(srv.done[r], ImageResult) for r in range(8))


def test_unverifiable_rung_is_skipped(lenet, monkeypatch):
    """A ladder rung whose plan fails static verification is never
    served: switch_verified rolls the knobs back and the server walks
    to the next rung."""
    net, _, params, imgs = lenet
    eng = CNNEngine(net, method=Method.ADVANCED_SIMD_8)

    def fake_verify(self, fuse=None):
        if self.method == Method.ADVANCED_SIMD_4:
            return [Finding("error", "plan", "V301", "injected bust")]
        return []

    monkeypatch.setattr(CNNEngine, "verify", fake_verify)
    ladder = (Rung(Method.ADVANCED_SIMD_8, True),
              Rung(Method.ADVANCED_SIMD_4, True),
              Rung(Method.BASIC_SIMD, True))
    ctl = DegradeController(ladder, queue_high=1, degrade_after=1,
                            cooldown=0)
    srv = _server(eng, params, max_batch=2, max_delay_s=0.0, degrade=ctl)
    _submit(srv, imgs, range(6))
    srv.step(force=True)                     # pressure -> down
    assert eng.method == Method.BASIC_SIMD   # skipped the rejected rung
    assert ctl.rung == 2
    rejected = [e for e in srv.events if e["kind"] == "rung_rejected"]
    assert len(rejected) == 1
    assert rejected[0]["rung"] == "advanced_simd_4/fused"


def test_switch_verified_rolls_back_on_error(lenet, monkeypatch):
    net, _, params, imgs = lenet
    eng = CNNEngine(net, method=Method.ADVANCED_SIMD_8)

    def fake_verify(self, fuse=None):
        if self.method == Method.ADVANCED_SIMD_4:
            return [Finding("error", "plan", "V301", "injected bust")]
        return []

    monkeypatch.setattr(CNNEngine, "verify", fake_verify)
    ok, findings = eng.switch_verified(method=Method.ADVANCED_SIMD_4)
    assert not ok and findings[0].rule == "V301"
    assert eng.method == Method.ADVANCED_SIMD_8      # rolled back
    ok, findings = eng.switch_verified(method=Method.BASIC_SIMD,
                                       fuse_pool=False)
    assert ok and eng.method == Method.BASIC_SIMD
    assert eng.fuse_pool is False
    with pytest.raises(ValueError, match="unknown knob"):
        eng.switch_verified(methd=Method.BASIC_SIMD)


def test_overload_burst_sheds_and_degrades(lenet):
    """The acceptance scenario: a scripted overload burst against a
    bounded queue triggers typed shedding AND at least one verified
    method-downgrade, all visible in stats()."""
    net, _, params, imgs = lenet
    eng = CNNEngine(net, method=Method.ADVANCED_SIMD_8)
    ladder = (Rung(Method.ADVANCED_SIMD_8, True),
              Rung(Method.ADVANCED_SIMD_4, True))
    ctl = DegradeController(ladder, queue_high=1, degrade_after=1,
                            recover_after=10 ** 9, cooldown=0)
    srv = _server(eng, params, max_batch=2, max_delay_s=0.0, max_queue=4,
                  degrade=ctl)
    sheds = [r for r in _submit(srv, imgs, range(10)) if r is not None]
    assert len(sheds) == 6                   # queue bound admits 4 of 10
    assert all(s.reason == "queue_full" for s in sheds)
    srv.run_until_drained()
    s = srv.stats()
    assert s["rejected"] == 6 and s["shed"] == 6
    assert s["degraded"] >= 1                # at least one verified downgrade
    assert s["served"] == 4
    assert eng.method == Method.ADVANCED_SIMD_4
    assert not any(f.severity == "error" for f in eng.verify())
    # shed requests resolved as typed results, served ones as ImageResults
    assert all(isinstance(srv.done[r], ShedResult) for r in range(4, 10))
    assert all(isinstance(srv.done[r], ImageResult) for r in range(4))
