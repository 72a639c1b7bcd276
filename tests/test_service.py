"""The serving layer: admission, scheduling, warm caches, faults, stats.

End-to-end: a warm service serves a concurrent mixed-signature stream with
zero kernel compiles after warm-up (every request a plan-cache hit) and
returns bit-identical results to the engine run of the same recorded
program.  Unit level: the scheduler's admission bound, priority order,
signature grouping and deadline expiry; the injected-fault
restore-and-continue path; retry exhaustion; the logged interpreter
degraded mode; and the per-request / service-level stats surfaces.
"""

import threading

import numpy as np
import pytest

from repro.compiler import stats as kstats
from repro.engine import hooks, reset_stats
from repro.engine.stats import stats as estats
from repro.runtime.fault import FaultInjector, InjectedFault
from repro.service import (
    DeadlineExceeded,
    PlanSignature,
    RequestFailed,
    ServiceOverloaded,
    SignatureScheduler,
    SimulationService,
    SolveRequest,
    StepRequest,
    Ticket,
    get_workload,
    service_stats,
)

SIGS = [
    PlanSignature("heat3d", (12, 10, 6)),
    PlanSignature("advdiff", (10, 10, 6)),
    PlanSignature("jacobi3d", (8, 8, 6), time_tile=2),
]
SOLVE_SIG = PlanSignature("btcs_heat", (8, 8, 6))


@pytest.fixture(scope="module")
def warm_service():
    reset_stats()
    svc = SimulationService(
        workers=2, capacity=512, manifest=SIGS + [SOLVE_SIG],
        default_chunk=4,
    )
    svc.start()
    yield svc
    svc.stop()


# -- request model ------------------------------------------------------------


def test_signature_key_and_json_roundtrip():
    sig = PlanSignature("heat3d", (4, 5, 6), dtype="float64", time_tile=3)
    assert sig.key() == "heat3d:4x5x6:float64:k3:pallas"
    assert PlanSignature.from_json(sig.to_json()) == sig


def test_request_validation():
    sig = SIGS[0]
    with pytest.raises(ValueError, match="shape must be"):
        PlanSignature("heat3d", (4, 5))
    with pytest.raises(ValueError, match="steps must be"):
        StepRequest(sig, steps=0)
    with pytest.raises(ValueError, match="requires an explicit ckpt_key"):
        StepRequest(sig, steps=1, resume=True)
    with pytest.raises(ValueError, match="init shape"):
        StepRequest(sig, steps=1, init=np.zeros((3, 3, 3), np.float32))
    with pytest.raises(KeyError, match="unknown workload"):
        get_workload("nope")


def test_ticket_timeout():
    t = Ticket(StepRequest(SIGS[0], steps=1))
    with pytest.raises(TimeoutError):
        t.result(timeout=0.01)
    assert not t.done() and t.error() is None


# -- scheduler ----------------------------------------------------------------


def _ticket(sig=None, priority=0, deadline_s=None):
    return Ticket(
        StepRequest(
            sig or SIGS[0], steps=1, priority=priority, deadline_s=deadline_s
        )
    )


def test_scheduler_admission_bound():
    sched = SignatureScheduler(capacity=2)
    sched.submit(_ticket())
    sched.submit(_ticket())
    with pytest.raises(ServiceOverloaded):
        sched.submit(_ticket())


def test_scheduler_priority_then_fifo():
    sched = SignatureScheduler(group_max=1)
    lo1, hi, lo2 = _ticket(priority=0), _ticket(priority=5), _ticket(priority=0)
    for t in (lo1, hi, lo2):
        sched.submit(t)
    order = [sched.get_group(timeout=1)[0] for _ in range(3)]
    assert order == [hi, lo1, lo2]


def test_scheduler_groups_by_signature():
    sched = SignatureScheduler(group_max=8)
    a1, b, a2 = _ticket(SIGS[0]), _ticket(SIGS[1]), _ticket(SIGS[0])
    for t in (a1, b, a2):
        sched.submit(t)
    group = sched.get_group(timeout=1)
    assert group == [a1, a2]  # same signature drained past the interloper
    assert sched.get_group(timeout=1) == [b]


def test_scheduler_group_max_caps_the_drain():
    sched = SignatureScheduler(group_max=2)
    tickets = [_ticket() for _ in range(5)]
    for t in tickets:
        sched.submit(t)
    assert len(sched.get_group(timeout=1)) == 2
    assert len(sched) == 3


def test_scheduler_expires_overdue_requests_at_dispatch():
    sched = SignatureScheduler()
    dead = _ticket(deadline_s=0.0)
    live = _ticket(SIGS[1])
    sched.submit(dead)
    sched.submit(live)
    group = sched.get_group(timeout=1)
    assert group == [live]
    assert sched.expired == [dead]
    with pytest.raises(DeadlineExceeded):
        dead.result(timeout=1)


def test_scheduler_close_drains_then_signals_exit():
    sched = SignatureScheduler()
    t = _ticket()
    sched.submit(t)
    sched.close()
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit(_ticket())
    assert sched.get_group(timeout=1) == [t]  # queued work still served
    assert sched.get_group(timeout=1) == []  # then the exit signal


# -- end-to-end serving -------------------------------------------------------


def _reference(sig: PlanSignature, steps: int) -> np.ndarray:
    """The engine's own answer for a workload signature (no service)."""
    from repro.engine.executor import run_program

    spec = get_workload(sig.workload)
    program, answer = spec.record(sig.shape, np.dtype(sig.dtype), steps)
    out = run_program(
        program, backend=sig.backend, time_tile=sig.time_tile
    )
    return out[answer]


def test_serves_concurrent_mixed_stream_with_zero_compiles(warm_service):
    svc = warm_service
    built = kstats.kernels_built
    tickets = []
    for i in range(64):
        if i % 8 == 7:
            tickets.append(svc.submit(SolveRequest(SOLVE_SIG, maxiter=40)))
        else:
            tickets.append(
                svc.submit(
                    StepRequest(SIGS[i % 3], steps=8, priority=i % 2)
                )
            )
    results = [t.result(timeout=300) for t in tickets]
    assert all(np.all(np.isfinite(np.asarray(r))) for r in results)
    assert len({t.stats.signature for t in tickets}) == 4
    # the warm-pool contract: no compiles, no plan builds, no retries
    assert kstats.kernels_built == built
    assert all(t.stats.plan_cache_hit for t in tickets)
    assert sum(t.stats.retries for t in tickets) == 0
    assert not any(t.stats.degraded for t in tickets)
    # per-request observability is populated
    st = tickets[0].stats
    assert st.steps == 8 and st.chunks == 2 and st.launches >= 2
    assert st.queue_wait_s >= 0.0 and st.latency_s > 0.0
    assert st.worker in (0, 1)


def test_service_results_match_engine_bitwise(warm_service):
    for sig in SIGS:
        t = warm_service.submit(StepRequest(sig, steps=9))
        out = t.result(timeout=300)
        ref = _reference(sig, 9)
        assert out.dtype == ref.dtype
        assert (out == ref).all(), sig.key()


def test_solve_request_converges(warm_service):
    t = warm_service.submit(SolveRequest(SOLVE_SIG, tol=1e-5, maxiter=80))
    out = t.result(timeout=300)
    assert np.all(np.isfinite(out))
    assert t.stats.iterations >= 1


def test_custom_init_overrides_default(warm_service):
    sig = SIGS[0]
    init = np.full(sig.shape, 7.25, np.float32)
    t = warm_service.submit(StepRequest(sig, steps=1, init=init))
    out = t.result(timeout=300)
    assert not np.allclose(out, _reference(sig, 1))


def test_submit_requires_started_service():
    svc = SimulationService(workers=1)
    with pytest.raises(RuntimeError, match="not started"):
        svc.submit(StepRequest(SIGS[0], steps=1))


def test_rejected_submission_counts(warm_service, monkeypatch):
    before = estats.requests_rejected

    def full(ticket):
        raise ServiceOverloaded("queue full (test)")

    monkeypatch.setattr(warm_service.scheduler, "submit", full)
    with pytest.raises(ServiceOverloaded):
        warm_service.submit(StepRequest(SIGS[0], steps=1))
    assert estats.requests_rejected == before + 1


# -- fault tolerance ----------------------------------------------------------


def test_injected_fault_completes_via_restore(warm_service, tmp_path):
    warm_service.ckpt_root = str(tmp_path)
    req = StepRequest(SIGS[0], steps=8, ckpt_every=2)
    with FaultInjector(fail_at=[4], match_tag=req.request_id):
        t = warm_service.submit(req)
        out = t.result(timeout=300)
    assert (out == _reference(SIGS[0], 8)).all()  # still bitwise
    assert t.stats.retries == 1 and t.stats.restores == 1
    assert t.stats.checkpoints == 4


def test_fault_without_checkpoints_restarts_from_scratch(warm_service):
    req = StepRequest(SIGS[1], steps=8)
    with FaultInjector(fail_at=[4], match_tag=req.request_id):
        t = warm_service.submit(req)
        out = t.result(timeout=300)
    assert (out == _reference(SIGS[1], 8)).all()
    assert t.stats.retries == 1 and t.stats.restores == 0


def test_compile_refusal_fails_fast_without_retries(warm_service):
    """An operation the compiler cannot lower (``NotImplementedError``) is
    permanent: the ticket fails on the first attempt, with no retry."""
    req = StepRequest(SIGS[0], steps=4, ckpt_every=2)

    def refuse(step, tag=""):
        if tag == req.request_id:
            raise NotImplementedError("no lowering for this op")

    prev = hooks.set_step_hook(refuse)
    try:
        t = warm_service.submit(req)
        with pytest.raises(NotImplementedError, match="no lowering"):
            t.result(timeout=300)
    finally:
        hooks.set_step_hook(prev)
    assert t.stats.retries == 0 and t.stats.restores == 0


def test_retry_budget_exhaustion_fails_the_ticket(warm_service):
    req = StepRequest(SIGS[0], steps=4)

    def always_fail(step, tag=""):
        if tag == req.request_id:
            raise InjectedFault("permanent injected fault")

    failed_before = estats.requests_failed
    prev = hooks.set_step_hook(always_fail)
    try:
        t = warm_service.submit(req)
        with pytest.raises(RequestFailed, match="after 3 retries"):
            t.result(timeout=300)
    finally:
        hooks.set_step_hook(prev)
    assert t.stats.retries == warm_service.max_retries + 1
    assert estats.requests_failed == failed_before + 1


def test_permanent_errors_do_not_burn_retries(warm_service):
    t = warm_service.submit(
        SolveRequest(SOLVE_SIG, method="not-a-method", maxiter=5)
    )
    with pytest.raises((ValueError, KeyError)):
        t.result(timeout=300)
    assert t.stats.retries == 0


def test_compile_failure_serves_degraded_and_logged(warm_service, caplog):
    degraded_sig = PlanSignature("advdiff", (11, 11, 6))  # plan-cache miss
    fb_before = kstats.fallbacks
    with caplog.at_level("WARNING"):
        with FaultInjector(fail_compile=["service_advdiff"]):
            t = warm_service.submit(StepRequest(degraded_sig, steps=4))
            out = t.result(timeout=300)
    assert np.all(np.isfinite(out))
    assert t.stats.degraded
    assert "injected compile failure" in t.stats.degraded_reason
    assert kstats.fallbacks == fb_before + 1
    assert any("DEGRADED" in r.message for r in caplog.records)
    # degraded is a mode, not an error: later requests for the same
    # signature reuse the interpreter plan and are flagged the same way
    t2 = warm_service.submit(StepRequest(degraded_sig, steps=2))
    t2.result(timeout=300)
    assert t2.stats.degraded and t2.stats.plan_cache_hit


def test_expired_deadline_fails_before_running(warm_service):
    t = warm_service.submit(
        StepRequest(SIGS[2], steps=2, deadline_s=0.0)
    )
    with pytest.raises(DeadlineExceeded):
        t.result(timeout=300)
    assert t.stats.steps == 0  # never dispatched to a chunk


# -- observability + manifest -------------------------------------------------


def test_service_stats_shape(warm_service):
    s = warm_service.service_stats()
    assert s["requests"]["completed"] >= 64
    assert s["plans"]["cache_hits"] >= 64
    assert s["kernels"]["cache_hits"] >= 0
    assert s["faults"]["checkpoints"] >= 1
    assert s["service"]["workers"] == 2
    assert set(s["service"]["plan_cache"]) >= {sig.key() for sig in SIGS}
    # the module-level accessor reads the same counters
    assert service_stats()["requests"] == s["requests"]


def test_manifest_roundtrip_warms_next_instance(tmp_path):
    path = str(tmp_path / "manifest.json")
    svc = SimulationService(workers=1, manifest=[SIGS[0]])
    svc.start()
    try:
        svc.submit(StepRequest(SIGS[1], steps=1)).result(timeout=300)
        svc.save_manifest(path)
    finally:
        svc.stop()

    svc2 = SimulationService(workers=1, manifest=path)
    assert {s.key() for s in svc2._manifest_sigs} == {
        SIGS[0].key(), SIGS[1].key(),
    }
    svc2.start()
    try:
        t = svc2.submit(StepRequest(SIGS[1], steps=2))
        t.result(timeout=300)
        assert t.stats.plan_cache_hit  # warmed from the manifest file
    finally:
        svc2.stop()


def test_straggler_flagging_reaches_service_stats():
    reset_stats()
    svc = SimulationService(
        workers=1, default_chunk=2, straggler_threshold=5.0
    )
    svc.start()
    try:
        sig = SIGS[0]
        # build a duration history, then slow one chunk 1000x
        svc.submit(StepRequest(sig, steps=8)).result(timeout=300)
        req = StepRequest(sig, steps=4)
        with FaultInjector(
            slow_at={2: 0.5}, match_tag=req.request_id
        ):
            svc.submit(req).result(timeout=300)
    finally:
        svc.stop()
    assert estats.service_stragglers >= 1


def test_worker_threads_exit_on_stop():
    svc = SimulationService(workers=2)
    svc.start()
    threads = list(svc._threads)
    svc.stop()
    assert all(not th.is_alive() for th in threads)
    assert threading.active_count() < 50  # no thread leak across tests
