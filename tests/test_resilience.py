"""Chaos suite: fault injection, admission control, degradation ladder.

Holds the serving tier to the resilience contract: with every catalogued
fault point armed, no SPC5Server call deadlocks, shed/expired/degraded
requests are typed and counted, and every non-shed request that resolves
with a result matches the reference oracle bit-for-bit. Fault sequences
are seed-pinned (repro.obs.faults), so a failure here replays.
"""
import collections
import concurrent.futures
import threading

import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import formats as F
from repro.core import matgen
from repro.core import plan as P
from repro.launch import resilience as R
from repro.launch import server as SV
from repro.obs import faults as FL


def _mat(dim=256, density=0.05, seed=0, rc=(1, 8)):
    csr = matgen.pruned_weight(dim, dim // 2, density, rc, seed=seed)
    return F.csr_to_spc5(csr, *rc)


PANELS = dict(layout="panels", pr=64, xw=16, cb=32, tune=False)


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test leaves the process-global fault registry disarmed."""
    prev = FL.set_faults(None)
    yield
    FL.set_faults(prev)


def _arm(spec):
    FL.set_faults(FL.Faults(spec))
    return FL.get_faults()


# ----------------------------------------------------------------------------
# repro.obs.faults: the injection registry itself
# ----------------------------------------------------------------------------

def test_parse_spec_grammar():
    assert FL.Faults.parse_spec("") == []
    assert FL.Faults.parse_spec("exec.spmv:0.5") == [("exec.spmv", 0.5, 0)]
    assert FL.Faults.parse_spec(" serve.exec:0.1:7 , plan.build:1 ") == \
        [("serve.exec", 0.1, 7), ("plan.build", 1.0, 0)]
    with pytest.raises(ValueError, match="expected point:rate"):
        FL.Faults.parse_spec("exec.spmv")
    with pytest.raises(ValueError, match="rate must be in"):
        FL.Faults.parse_spec("exec.spmv:1.5")
    # unknown names fail loudly at parse time, with a did-you-mean
    with pytest.raises(ValueError, match="did you mean 'serve.gather'"):
        FL.Faults.parse_spec("serve.gathr:0.1")


def test_catalogue_is_the_closed_point_set():
    # every catalogued point parses; the registry exposes exactly them
    spec = ",".join(f"{p}:0.1" for p in FL.CATALOGUE)
    f = FL.Faults(spec)
    assert f.points == tuple(sorted(FL.CATALOGUE))
    assert bool(f) and f.enabled


def test_deterministic_seeded_draws():
    seq = [FL.Faults("exec.spmv:0.3:42").check("exec.spmv")
           for _ in range(1)]  # noqa: F841 -- shape check below is the test
    a = FL.Faults("exec.spmv:0.3:42")
    b = FL.Faults("exec.spmv:0.3:42")
    draws_a = [a.check("exec.spmv") for _ in range(64)]
    draws_b = [b.check("exec.spmv") for _ in range(64)]
    assert draws_a == draws_b and any(draws_a) and not all(draws_a)
    # a different seed is a different sequence; rates 0/1 are exact
    c = FL.Faults("exec.spmv:0.3:43")
    assert [c.check("exec.spmv") for _ in range(64)] != draws_a
    assert not any(FL.Faults("exec.spmv:0:1").check("exec.spmv")
                   for _ in range(16))
    assert all(FL.Faults("exec.spmv:1:1").check("exec.spmv")
               for _ in range(16))


def test_points_draw_independently():
    # one point's firing sequence never shifts another's
    lone = FL.Faults("exec.spmv:0.5:9")
    seq_lone = [lone.check("exec.spmv") for _ in range(32)]
    both = FL.Faults("exec.spmv:0.5:9,serve.exec:0.5:1")
    seq_both = []
    for _ in range(32):
        both.check("serve.exec")            # interleaved draws elsewhere
        seq_both.append(both.check("exec.spmv"))
    assert seq_both == seq_lone


def test_maybe_fail_stats_and_unarmed_points():
    f = FL.Faults("exec.spmv:1:0")
    with pytest.raises(FL.FaultError) as e:
        f.maybe_fail("exec.spmv")
    assert e.value.point == "exec.spmv"
    assert not f.check("serve.exec")        # unarmed: never fires
    f.maybe_fail("serve.exec")
    st = f.stats()
    assert st == {"exec.spmv": {"rate": 1.0, "seed": 0,
                                "checks": 1, "fired": 1}}


def test_suppress_is_thread_local():
    f = FL.Faults("exec.spmv:1:0")
    other_thread = {}

    def probe():
        other_thread["fired"] = f.check("exec.spmv")

    with f.suppress():
        assert not f.check("exec.spmv")
        t = threading.Thread(target=probe)
        t.start()
        t.join()
    assert other_thread["fired"]            # chaos elsewhere undisturbed
    assert f.check("exec.spmv")             # and restored on this thread


def test_null_faults_and_global_registry():
    assert not FL.NULL_FAULTS.check("exec.spmv")
    assert not FL.NULL_FAULTS.enabled and not bool(FL.NULL_FAULTS)
    FL.NULL_FAULTS.maybe_fail("exec.spmv")  # never raises
    assert FL.get_faults() is FL.NULL_FAULTS
    armed = FL.Faults("exec.spmv:1:0")
    assert FL.set_faults(armed) is FL.NULL_FAULTS
    assert FL.get_faults() is armed
    assert FL.set_faults(None) is armed     # None disarms
    assert FL.get_faults() is FL.NULL_FAULTS
    assert FL.faults_from_env({}) is FL.NULL_FAULTS
    env = {"SPC5_FAULTS": "serve.exec:0.25:3"}
    assert FL.faults_from_env(env).points == ("serve.exec",)


# ----------------------------------------------------------------------------
# repro.launch.resilience: ladder, breaker, supervisor
# ----------------------------------------------------------------------------

def test_ladder_rungs_from_auto_request():
    rungs = list(R.ladder_requests({"vdtype": "auto"}))
    assert [r[0] for r in rungs] == ["f32-values", "reference"]
    assert rungs[0][1]["vdtype"] == "f32"
    ref = rungs[1][1]
    assert ref["tune"] is False and ref["reorder"] is None
    # only the reference rung runs with injection suppressed
    assert [r[2] for r in rungs] == [False, True]


def test_ladder_skips_noop_rungs_and_drops_geometry():
    # already at f32: demotion starts at the reference rung
    assert [r[0] for r in R.ladder_requests(dict(PANELS, vdtype="f32"))
            ] == ["reference"]
    rungs = list(R.ladder_requests(dict(PANELS)))
    assert [r[0] for r in rungs] == ["f32-values", "reference"]
    # the reference rung sheds explicit layout/geometry and the legacy
    # dtype passthrough -- the minimal trusted build
    ref = rungs[-1][1]
    for k in ("layout", "pr", "xw", "cb", "dtype"):
        assert k not in ref
    # a request already minimal yields only real demotions
    minimal = {"vdtype": "f32", "tune": False, "reorder": None}
    assert list(R.ladder_requests(minimal)) == []


def test_circuit_breaker_trip_halfopen_close():
    br = R.CircuitBreaker(threshold=2, reset_s=0.05)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.allow()                       # below threshold
    br.record_failure()
    assert br.state == "open" and not br.allow()
    import time
    time.sleep(0.06)
    assert br.state == "half-open"
    assert br.allow()                       # ONE probe gets through
    assert not br.allow()                   # second caller still blocked
    br.record_success()
    assert br.state == "closed" and br.allow()
    # a failed probe re-opens for another reset window
    br.record_failure(), br.record_failure()
    time.sleep(0.06)
    assert br.allow()
    br.record_failure()
    assert not br.allow()


def test_circuit_breaker_force_open_latches():
    br = R.CircuitBreaker(threshold=2, reset_s=0.0)
    br.force_open()
    assert br.state == "open" and not br.allow()
    br.record_success()                     # nothing un-latches it
    assert not br.allow()


def test_supervised_worker_restarts_and_streak_reset():
    reg = obs.Registry()
    restarts = reg.counter("t_restarts", "")
    calls = {"n": 0}

    def iteration():
        calls["n"] += 1
        if calls["n"] in (1, 2, 4):         # crash, crash, ok, crash, done
            raise RuntimeError(f"crash {calls['n']}")
        if calls["n"] >= 5:
            return R.DONE
        return None

    w = R.SupervisedWorker("t", iteration, restarts=restarts,
                           max_restarts=2, backoff_s=0.001).start()
    assert w.join(5)
    assert w.done and not w.gave_up
    assert w.crashes == 3 and restarts.value == 3
    assert calls["n"] == 5                  # streak reset kept it alive


def test_supervised_worker_gives_up_after_budget():
    gave = []

    def iteration():
        raise RuntimeError("hard wedge")

    w = R.SupervisedWorker("t", iteration, max_restarts=2, backoff_s=0.001,
                           on_give_up=gave.append).start()
    assert w.join(5)
    assert w.gave_up and w.done
    assert w.crashes == 3                   # budget + the final straw
    assert len(gave) == 1 and "hard wedge" in str(gave[0])
    assert "hard wedge" in str(w.last_error)


# ----------------------------------------------------------------------------
# Build-side ladder: PlanCache.get_or_build under injected failures
# ----------------------------------------------------------------------------

def test_cache_build_ladder_lands_on_reference():
    _arm("plan.build:1:0")                  # EVERY unsuppressed build fails
    mat = _mat()
    cache = SV.PlanCache()
    plan = cache.get_or_build(mat, **PANELS)
    # only the suppressed reference rung can have built this plan
    degrade = [e for e in plan.trace if e["pass"] == "degrade"]
    assert [e["rung"] for e in degrade] == ["f32-values", "reference"]
    assert all("FaultError" in e["reason"] for e in degrade)
    assert all(e["duration_s"] >= 0 for e in degrade)
    assert cache.stats()["degraded"] == 1
    # and it still computes the right answer
    FL.set_faults(None)
    x = jnp.ones(mat.shape[1], jnp.float32)
    ref = SV.PlanCache().get_or_build(mat, **PANELS)
    np.testing.assert_allclose(np.asarray(P.execute_spmv(plan, x)),
                               np.asarray(P.execute_spmv(ref, x)),
                               rtol=1e-5)


def test_cache_admit_fault_degrades_like_verify_failure():
    _arm("cache.admit:1:0")
    cache = SV.PlanCache(verify_on_admit=True)
    plan = cache.get_or_build(_mat(), **PANELS)
    rungs = [e["rung"] for e in plan.trace if e["pass"] == "degrade"]
    assert rungs and rungs[-1] == "reference"
    # the degraded plan passes the very verifier admission runs
    from repro.analysis.verify import verify_plan
    verify_plan(plan).raise_if_failed()


def test_cache_degrade_off_raises():
    _arm("plan.build:1:0")
    cache = SV.PlanCache(degrade=False)
    with pytest.raises(FL.FaultError):
        cache.get_or_build(_mat(), **PANELS)
    assert len(cache) == 0 and cache.stats()["degraded"] == 0


def test_cache_partial_ladder_uses_first_working_rung():
    # builder that only fails for a non-f32 vdtype: the ladder stops at
    # the f32 rung, never reaching the reference
    from repro.kernels import ops
    calls = []

    def builder(m, **kw):
        calls.append(dict(kw))
        if kw.get("vdtype") != "f32":
            raise RuntimeError("quantised store corrupt")
        return ops.prepare(m, **kw)

    cache = SV.PlanCache(builder=builder)
    plan = cache.get_or_build(_mat(), vdtype="bf16", **PANELS)
    rungs = [e["rung"] for e in plan.trace if e["pass"] == "degrade"]
    assert rungs == ["f32-values"]
    assert calls[-1]["vdtype"] == "f32"


# ----------------------------------------------------------------------------
# Admission control: validation, shedding, deadlines, submit/close race
# ----------------------------------------------------------------------------

def _server(plan, **kw):
    kw.setdefault("window_us", 200)
    kw.setdefault("max_batch", 8)
    return SV.SPC5Server(plan, **kw)


@pytest.fixture(scope="module")
def plan():
    return SV.PlanCache().get_or_build(_mat(), **PANELS)


def test_submit_validation_rejects_poison_alone(plan):
    ncols = dict(plan.meta)["ncols"]
    with _server(plan, window_us=20000, max_batch=8) as srv:
        good = jnp.ones(ncols, jnp.float32)
        bad_nan = jnp.full(ncols, jnp.nan, jnp.float32)
        f1 = srv.submit(good)
        with pytest.raises(ValueError, match="non-finite"):
            srv.submit(bad_nan)
        with pytest.raises(ValueError, match="shape"):
            srv.submit(jnp.ones(ncols + 1, jnp.float32))
        with pytest.raises(ValueError, match="floating"):
            srv.submit(jnp.ones(ncols, jnp.int32))
        with pytest.raises(ValueError):
            srv.submit(jnp.ones((2, ncols), jnp.float32))
        # the batch the poison would have ridden in is unharmed
        np.testing.assert_array_equal(
            np.asarray(f1.result(timeout=60)),
            np.asarray(P.execute_spmv(plan, good)))
        assert srv.stats()["invalid"] == 4


def test_admission_bound_sheds_instead_of_queueing(plan):
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    # a huge window holds the first batch open while we flood the queue
    with _server(plan, window_us=500000, max_batch=1,
                 max_pending=4) as srv:
        admitted, shed = [], 0
        for _ in range(64):
            try:
                admitted.append(srv.submit(x))
            except R.ShedError:
                shed += 1
        assert shed > 0
        assert len(srv._pending) <= srv.max_pending     # the bound HELD
        assert srv.stats()["shed"] == shed
        ref = np.asarray(P.execute_spmv(plan, x))
        for f in admitted:                  # everything admitted is served
            np.testing.assert_array_equal(np.asarray(f.result(timeout=60)),
                                          ref)


def test_deadline_drops_before_dispatch(plan):
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    # the coalescing window (50ms) outlives the deadline (1ms): the
    # request must expire inside the window, not compute-then-discard
    with _server(plan, window_us=50000, max_batch=8) as srv:
        doomed = srv.submit(x, deadline_s=0.001)
        live = srv.submit(x)                # no deadline: must survive
        with pytest.raises(R.DeadlineExceededError):
            doomed.result(timeout=60)
        np.testing.assert_array_equal(
            np.asarray(live.result(timeout=60)),
            np.asarray(P.execute_spmv(plan, x)))
        assert srv.stats()["expired"] == 1


def test_deadline_propagation_property(plan):
    """Seeded property test: through any coalescing interleaving, a
    request with an already-unreachable deadline NEVER yields a result,
    one with a generous deadline ALWAYS does, and everything in between
    resolves to exactly one of {result, DeadlineExceededError}."""
    ncols = dict(plan.meta)["ncols"]
    rng = np.random.default_rng(11)
    x = jnp.ones(ncols, jnp.float32)
    ref = np.asarray(P.execute_spmv(plan, x))
    with _server(plan, window_us=5000, max_batch=4,
                 deadline_s=0.0) as srv:
        futs = []
        for _ in range(48):
            kind = rng.integers(0, 3)
            if kind == 0:       # tighter than the window: must expire
                dl = float(rng.uniform(1e-6, 1e-4))
            elif kind == 1:     # far beyond any queueing: must land
                dl = 60.0
            else:               # adversarial middle ground
                dl = float(rng.uniform(1e-3, 2e-2))
            futs.append((kind, srv.submit(x, deadline_s=dl)))
        for kind, f in futs:
            try:
                y = f.result(timeout=60)
                assert kind != 0, "sub-window deadline produced a result"
                np.testing.assert_array_equal(np.asarray(y), ref)
            except R.DeadlineExceededError:
                assert kind != 1, "generous deadline expired"
        st = srv.stats()
        assert st["expired"] >= sum(1 for k, _ in futs if k == 0)
        assert st["expired"] + st["requests"] >= len(futs)


def test_submit_after_close_races_cleanly(plan):
    """The closed-check happens under the queue lock: a submit racing
    close either lands (and is served/cancelled) or raises RuntimeError
    -- never a silently dropped future."""
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    outcomes = collections.Counter()
    srv = _server(plan)
    futs = []

    def hammer():
        for _ in range(200):
            try:
                futs.append(srv.submit(x))
                outcomes["admitted"] += 1
            except RuntimeError:            # includes ShedError subtype
                outcomes["refused"] += 1

    t = threading.Thread(target=hammer)
    t.start()
    srv.close()
    t.join()
    assert outcomes["admitted"] + outcomes["refused"] == 200
    done = concurrent.futures.wait(futs, timeout=60)
    assert not done.not_done                # every admitted future resolved


def test_close_cancels_outstanding_and_reports_stuck(plan, monkeypatch):
    ncols = dict(plan.meta)["ncols"]
    x = jnp.ones(ncols, jnp.float32)
    unwedge = threading.Event()
    orig = P.execute_spmv

    def wedged(plan_, x_, **kw):
        unwedge.wait(30)
        return orig(plan_, x_, **kw)

    monkeypatch.setattr(P, "execute_spmv", wedged)
    srv = _server(plan, max_batch=1, prefetch_depth=1)
    futs = [srv.submit(x) for _ in range(6)]
    with pytest.raises(RuntimeError, match="still running"):
        srv.close(timeout=0.3)              # a hung close is LOUD
    unwedge.set()
    # no future is abandoned: each resolves (result from the drain) or
    # was cancelled by close
    done = concurrent.futures.wait(futs, timeout=60)
    assert not done.not_done
    kinds = {("cancelled" if f.cancelled() else "resolved") for f in futs}
    assert "cancelled" in kinds or "resolved" in kinds


def test_close_is_idempotent_and_drains(plan):
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    srv = _server(plan)
    futs = [srv.submit(x) for _ in range(8)]
    srv.close()
    srv.close()                             # idempotent
    ref = np.asarray(P.execute_spmv(plan, x))
    for f in futs:                          # close() drains, never drops
        np.testing.assert_array_equal(np.asarray(f.result(timeout=60)), ref)
    with pytest.raises(RuntimeError):
        srv.submit(x)


# ----------------------------------------------------------------------------
# Supervised workers + exec ladder under injected crashes
# ----------------------------------------------------------------------------

def test_worker_crashes_restart_without_losing_requests(plan):
    _arm("serve.gather:0.4:5,serve.exec:0.4:6")
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    ref = np.asarray(P.execute_spmv(plan, x))
    with _server(plan) as srv:
        futs = [srv.submit(x) for _ in range(24)]
        for f in futs:
            np.testing.assert_array_equal(np.asarray(f.result(timeout=60)),
                                          ref)
        assert srv.stats()["worker_restarts"] >= 1


def test_exec_ladder_serves_through_kernel_faults(plan):
    _arm("exec.spmv:1:0,exec.spmm:1:0")     # every tuned dispatch fails
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    with _server(plan, window_us=20000, max_batch=8) as srv:
        futs = [srv.submit(x) for _ in range(8)]
        ys = [np.asarray(f.result(timeout=60)) for f in futs]
        st = srv.stats()
        assert st["degraded"] >= 1          # the oracle rung served them
    FL.set_faults(None)
    ref = np.asarray(P.execute_spmv(plan, x))
    for y in ys:
        np.testing.assert_allclose(y, ref, rtol=1e-5)


def test_wedged_tier_opens_breaker_and_fails_fast(plan):
    _arm("serve.exec:1:0")                  # the executor cannot run AT ALL
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    srv = _server(plan, max_restarts=1)
    try:
        fut = srv.submit(x)
        # the worker exhausts its consecutive-crash budget, latches the
        # breaker, and fails what was queued -- nothing hangs
        with pytest.raises(R.CircuitOpenError):
            fut.result(timeout=30)
        deadline = obs.monotonic() + 30
        while srv._breaker.state != "open" and obs.monotonic() < deadline:
            pass
        with pytest.raises(R.CircuitOpenError):
            srv.submit(x)
        assert srv._exec_worker.gave_up
    finally:
        FL.set_faults(None)
        # even with the executor gone, close terminates cleanly: the
        # gather worker notices the dead peer (or the cleared queue) and
        # exits, and leftovers -- there are none, give-up failed them
        # all -- would be cancelled
        srv.close(timeout=10)


def test_no_degrade_server_fails_callers_typed(plan):
    _arm("exec.spmv:1:0,exec.spmm:1:0")
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    with _server(plan, degrade=False) as srv:
        fut = srv.submit(x)
        with pytest.raises(FL.FaultError):
            fut.result(timeout=60)


# ----------------------------------------------------------------------------
# The acceptance storm: every catalogued point at 10%, threaded clients
# ----------------------------------------------------------------------------

def test_chaos_storm_all_points_ten_percent():
    mat = _mat(seed=7)
    ref_plan = SV.PlanCache().get_or_build(mat, **PANELS)
    x_pool = [jnp.asarray(np.random.default_rng(i).standard_normal(
        mat.shape[1]), jnp.float32) for i in range(4)]
    refs = [np.asarray(P.execute_spmv(ref_plan, x)) for x in x_pool]

    spec = ",".join(f"{p}:0.1:{i}" for i, p in enumerate(sorted(
        FL.CATALOGUE)))
    _arm(spec)
    cache = SV.PlanCache(verify_on_admit=True)
    plan = cache.get_or_build(mat, **PANELS)
    srv = SV.SPC5Server(plan, window_us=500, max_batch=8, max_pending=64)
    outcomes = collections.Counter()
    mismatches = []
    lock = threading.Lock()

    def client(tid):
        rng = np.random.default_rng(tid)
        for i in range(20):
            j = int(rng.integers(0, len(x_pool)))
            try:
                fut = srv.submit(x_pool[j])
            except R.ShedError:
                with lock:
                    outcomes["shed"] += 1
                continue
            except R.CircuitOpenError:
                with lock:
                    outcomes["breaker"] += 1
                continue
            try:
                y = np.asarray(fut.result(timeout=60))
            except R.DeadlineExceededError:
                with lock:
                    outcomes["expired"] += 1
                continue
            except concurrent.futures.CancelledError:
                with lock:
                    outcomes["cancelled"] += 1
                continue
            with lock:
                outcomes["ok"] += 1
                if not np.allclose(y, refs[j], rtol=1e-5):
                    mismatches.append((tid, i))

    threads = [threading.Thread(target=client, args=(t,)) for t in range(6)]
    t0 = obs.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(not t.is_alive() for t in threads), "a client hung"
    elapsed = obs.monotonic() - t0
    srv.close()

    # the contract: nothing deadlocked, nothing hung past its deadline,
    # and every request that RESOLVED with a result matched the oracle
    assert mismatches == []
    assert outcomes["ok"] >= 1
    assert sum(outcomes.values()) == 6 * 20
    assert elapsed < 120
    st = srv.stats()
    assert st["requests"] == outcomes["ok"]
    fr = FL.get_faults()
    stats = fr.stats()
    # the serving-path points really drew under the storm
    for point in ("serve.gather", "serve.exec"):
        assert stats[point]["checks"] > 0


def test_chaos_storm_survives_every_single_point():
    """One point at a time at 100%: the tier still answers (ladder or
    supervisor), proving each wired point is individually survivable."""
    mat = _mat(seed=8)
    x = jnp.ones(mat.shape[1], jnp.float32)
    for point in ("plan.build", "cache.admit", "exec.spmv", "exec.spmm",
                  "serve.gather"):
        # (serve.exec at 100% is the wedged-tier case, tested above)
        rate = 1.0 if point in ("plan.build", "cache.admit",
                                "exec.spmv", "exec.spmm") else 0.5
        _arm(f"{point}:{rate}:0")
        cache = SV.PlanCache(verify_on_admit=True)
        plan = cache.get_or_build(mat, **PANELS)
        with SV.SPC5Server(plan, window_us=500, max_batch=4) as srv:
            futs = [srv.submit(x) for _ in range(6)]
            ys = [np.asarray(f.result(timeout=60)) for f in futs]
        FL.set_faults(None)
        ref = np.asarray(P.execute_spmv(
            SV.PlanCache().get_or_build(mat, **PANELS), x))
        for y in ys:
            np.testing.assert_allclose(y, ref, rtol=1e-5)


# ----------------------------------------------------------------------------
# open_loop: honest error accounting
# ----------------------------------------------------------------------------

class _ScriptedServer:
    """A stub whose submit outcomes are scripted: cycles through success,
    shed, synchronous failure, and a future that fails asynchronously."""

    def __init__(self):
        self.n = 0

    def spmv(self, x, timeout=None):
        return x

    def submit(self, x, **kw):
        self.n += 1
        mode = self.n % 4
        if mode == 1:
            raise R.ShedError("scripted shed")
        fut = concurrent.futures.Future()
        if mode == 2:
            fut.set_exception(RuntimeError("scripted failure"))
        elif mode == 3:
            fut.set_exception(R.DeadlineExceededError("scripted expiry"))
        else:
            fut.set_result(x)
        return fut


def test_open_loop_counts_failures_as_errors_not_latency():
    srv = _ScriptedServer()
    res = SV.open_loop(srv, [jnp.ones(4)], qps=400, duration_s=0.1,
                       seed=3, warmup=0)
    assert res["submitted"] == res["completed"] + res["shed"] + \
        res["expired"] + res["errors"]
    assert res["shed"] > 0 and res["errors"] > 0 and res["expired"] > 0
    # achieved QPS counts SUCCESSES only -- failures cannot flatter it
    assert res["completed"] < res["submitted"]
    assert res["qps_achieved"] == pytest.approx(
        res["completed"] / res["elapsed_s"])


def test_open_loop_full_success_path_unchanged(plan):
    xs = [jnp.ones(dict(plan.meta)["ncols"], jnp.float32)]
    with _server(plan, window_us=500, max_batch=16) as srv:
        res = SV.open_loop(srv, xs, qps=200, duration_s=0.2, seed=7)
    assert res["completed"] >= 1
    assert res["shed"] == res["expired"] == res["errors"] == 0
    assert 0 < res["p50_us"] <= res["p99_us"]


def test_serve_config_resilience_knobs_flow_to_tier():
    mat = _mat(seed=9)
    cfg = SV.ServeConfig(panel="64,16,32", max_pending=7,
                         deadline_ms=250.0, cache_mb=8)
    with SV.start(cfg, mat=mat) as srv:
        assert srv.max_pending == 7
        assert srv.deadline_s == pytest.approx(0.25)
        assert srv.degrade
    cfg2 = SV.ServeConfig(panel="64,16,32",
                          no_degrade=True, cache_mb=8,
                          faults="exec.spmv:0:0")
    try:
        with SV.start(cfg2, mat=mat) as srv:
            assert not srv.degrade and not srv.cache.degrade
            assert FL.get_faults().points == ("exec.spmv",)
    finally:
        FL.set_faults(None)


def test_serve_config_argparse_includes_resilience_knobs():
    import argparse
    ap = argparse.ArgumentParser()
    SV.add_config_args(ap)
    args = ap.parse_args(["--max-pending", "32", "--deadline-ms", "5",
                          "--faults", "serve.exec:0.1:7", "--no-degrade"])
    cfg = SV.config_from_args(args)
    assert cfg.max_pending == 32 and cfg.deadline_ms == 5.0
    assert cfg.faults == "serve.exec:0.1:7" and cfg.no_degrade
