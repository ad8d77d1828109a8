"""repro.obs: instruments, spans, exporters, and their serving-tier views.

Four properties the rest of the repo leans on, pinned here:

  * bucketed percentiles agree with numpy's sorted percentiles within one
    bucket ratio (the tolerance ``Histogram`` documents);
  * the disabled path is shared no-op singletons (no state, no spans);
  * counters stay exact under thread storms (Counter directly, and the
    PlanCache hit/miss totals through the serving tier);
  * every exporter round-trips (JSON snapshot <-> registry, Prometheus
    text <-> samples);
  * spans are on the profiler's clock: an enabled registry's ``with``
    spans show, nested, on a ``jax.profiler`` trace's ``/host:CPU`` plane.
"""
import glob
import json
import os
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import export as E
from repro.obs import metrics as M


# ----------------------------------------------------------------------------
# Histogram percentiles
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("q", [50, 90, 99])
def test_histogram_percentile_parity_with_numpy(q):
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-7.0, sigma=1.0, size=5000)   # ~1ms latencies
    h = M.Histogram("lat")
    for x in xs:
        h.observe(float(x))
    got = h.percentile(q)
    want = float(np.percentile(xs, q))
    # interpolation error is bounded by one log-bucket ratio (~1.26x)
    assert want / M.BUCKET_RATIO <= got <= want * M.BUCKET_RATIO


def test_histogram_edge_cases():
    h = M.Histogram("h")
    assert h.percentile(50) == 0.0 and h.count == 0
    h.observe(3e-3)
    # single sample: clamped to the observed min == max
    assert h.percentile(50) == pytest.approx(3e-3)
    assert h.percentile(99) == pytest.approx(3e-3)
    assert (h.min, h.max, h.mean) == (3e-3, 3e-3, 3e-3)
    h.observe(1e9)                         # beyond the last bound: overflow
    assert h.count == 2 and h.max == 1e9
    assert h.percentile(99) <= 1e9


def test_open_loop_percentiles_come_from_the_shared_histogram():
    # open_loop's p50/p99 are Histogram.percentile views -- pin the parity
    # contract at the instrument level: identical samples, identical answer
    samples = np.random.default_rng(1).lognormal(-8.0, 0.7, 2000)
    h1, h2 = M.Histogram("a"), M.Histogram("b")
    for s in samples:
        h1.observe(float(s))
        h2.observe(float(s))
    assert h1.percentile(50) == h2.percentile(50)
    assert h1.percentile(99) == h2.percentile(99)
    want = float(np.percentile(samples, 99))
    assert want / M.BUCKET_RATIO <= h1.percentile(99) <= want * M.BUCKET_RATIO


# ----------------------------------------------------------------------------
# Registry + the disabled path
# ----------------------------------------------------------------------------

def test_registry_get_or_create_and_kind_mismatch():
    reg = M.Registry()
    c = reg.counter("x_total", "help text")
    assert reg.counter("x_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("x_total")
    assert sorted(reg.instruments()) == ["x_total"]


def test_disabled_registry_is_noop_singletons():
    reg = M.Registry(enabled=False)
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    assert c is M.NULL_COUNTER and g is M.NULL_GAUGE \
        and h is M.NULL_HISTOGRAM
    c.inc(5)
    g.set(7.0)
    g.set_max(9.0)
    h.observe(1.0)
    assert (c.value, g.value, h.count) == (0, 0.0, 0)
    assert reg.instruments() == {}
    with reg.span("work", k=1) as sp:
        pass
    assert sp.span_id == 0 and sp.duration_s == 0.0
    assert reg.spans() == []


def test_counter_exact_under_thread_storm():
    c = M.Counter("c")
    n_threads, n_inc = 8, 10_000
    ts = [threading.Thread(target=lambda: [c.inc() for _ in range(n_inc)])
          for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_inc


def test_plan_cache_totals_exact_under_thread_storm():
    from repro.core import formats as F, matgen
    from repro.launch import server as SV

    csr = matgen.pruned_weight(256, 128, 0.05, (1, 8), seed=0)
    mat = F.csr_to_spc5(csr, 1, 8)
    cache = SV.PlanCache(capacity_bytes=1 << 30)
    req = dict(layout="whole_vector", cb=64, tune=False)
    n_threads, n_calls = 8, 25
    errs = []

    def storm():
        try:
            for _ in range(n_calls):
                cache.get_or_build(mat, **req)
        except Exception as e:  # noqa: BLE001 -- surfaced below
            errs.append(e)

    ts = [threading.Thread(target=storm) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    # every call increments exactly one of hits/misses under the lock
    assert cache.hits + cache.misses == n_threads * n_calls
    assert cache.misses >= 1 and len(cache) == 1


# ----------------------------------------------------------------------------
# Spans
# ----------------------------------------------------------------------------

def test_span_nesting_and_attrs():
    reg = M.Registry()
    with reg.span("outer", layer=1) as so:
        with reg.span("inner") as si:
            pass
    evs = {e.name: e for e in reg.spans()}
    assert evs["inner"].parent_id == so.span_id
    assert evs["outer"].parent_id is None
    assert evs["outer"].attrs == {"layer": 1}
    assert evs["inner"].t_start >= evs["outer"].t_start
    assert si.duration_s >= 0.0 and so.duration_s >= si.duration_s


def test_span_cross_thread_parent_propagation():
    reg = M.Registry()
    ctx = {}

    def worker():
        # the consumer side of submit -> exec: parent crosses the thread
        with reg.span("exec", parent=ctx["submit"]):
            pass

    with reg.span("submit") as sp:
        ctx["submit"] = sp.span_id
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    evs = {e.name: e for e in reg.spans()}
    assert evs["exec"].parent_id == sp.span_id
    assert evs["exec"].thread_id != evs["submit"].thread_id


def test_span_buffer_is_bounded():
    reg = M.Registry(max_spans=4)
    for i in range(10):
        with reg.span(f"s{i}"):
            pass
    names = [e.name for e in reg.spans()]
    assert names == ["s6", "s7", "s8", "s9"]            # oldest dropped


def test_global_registry_span_and_swap():
    prev = obs.set_registry(M.Registry())
    try:
        with obs.span("global.work") as sp:
            pass
        assert any(e.span_id == sp.span_id
                   for e in obs.get_registry().spans())
        assert "global.work" not in {e.name for e in prev.spans()}
    finally:
        obs.set_registry(prev)


# ----------------------------------------------------------------------------
# Spans on the profiler's clock
# ----------------------------------------------------------------------------

def _profiled_host_events(tmp_path, body):
    """Run ``body`` under ``jax.profiler`` and return the ``/host:CPU``
    events of the trace as ``{name: [(start_ns, end_ns), ...]}``."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_spans_are_mirrored_on_the_profiler_host_plane(tmp_path):
    reg = M.Registry()

    def body():
        with reg.span("obs_test.parent", n=3):
            with reg.span("obs_test.child"):
                pass
        # a begin()/finish() handle may end on another thread: not mirrored
        reg.begin_span("obs_test.manual").finish()

    evs = _profiled_host_events(tmp_path, body)
    (parent,) = evs["obs_test.parent"]
    (child,) = evs["obs_test.child"]
    assert parent[0] <= child[0] and child[1] <= parent[1]
    assert "obs_test.manual" not in evs
    # the registry keeps its own perf_counter record of all three
    assert [e.name for e in reg.spans()] == [
        "obs_test.child", "obs_test.parent", "obs_test.manual"]


def test_disabled_registry_puts_no_span_in_the_trace(tmp_path):
    reg = M.Registry(enabled=False)

    def body():
        with reg.span("obs_test.off_parent"):
            with reg.span("obs_test.off_child"):
                pass

    evs = _profiled_host_events(tmp_path, body)
    assert not [n for n in evs if n.startswith("obs_test.")]
    assert reg.spans() == []


# ----------------------------------------------------------------------------
# Exporters
# ----------------------------------------------------------------------------

def _loaded_registry():
    reg = M.Registry()
    reg.counter("req_total", "requests").inc(42)
    reg.gauge("widest").set(7.0)
    h = reg.histogram("lat_seconds", "latency")
    for x in np.random.default_rng(2).lognormal(-7.0, 1.0, 500):
        h.observe(float(x))
    with reg.span("unit.work", n=3):
        pass
    return reg


def test_snapshot_round_trip():
    reg = _loaded_registry()
    snap = json.loads(json.dumps(E.snapshot(reg)))      # through JSON
    reg2 = E.load_snapshot(snap)
    assert reg2.counter("req_total").value == 42
    assert reg2.gauge("widest").value == 7.0
    h1, h2 = reg.histogram("lat_seconds"), reg2.histogram("lat_seconds")
    assert (h2.count, h2.sum) == (h1.count, h1.sum)
    for q in (50, 99):
        assert h2.percentile(q) == h1.percentile(q)
    assert snap["histograms"]["lat_seconds"]["p50"] == h1.percentile(50)
    assert snap["spans"][0]["name"] == "unit.work"


def test_prometheus_round_trip():
    reg = _loaded_registry()
    text = E.to_prometheus(reg)
    assert "# TYPE req_total counter" in text
    assert "# HELP req_total requests" in text
    samples = E.parse_prometheus(text)
    assert samples["req_total"] == 42.0
    assert samples["widest"] == 7.0
    assert samples["lat_seconds_count"] == 500.0
    h = reg.histogram("lat_seconds")
    assert samples["lat_seconds_sum"] == pytest.approx(h.sum, rel=1e-6)
    # cumulative buckets: the +Inf sample equals the total count
    assert samples['lat_seconds_bucket{le="+Inf"}'] == 500.0


def test_dump_json_and_prometheus_files(tmp_path):
    reg = _loaded_registry()
    jpath, ppath = str(tmp_path / "obs.json"), str(tmp_path / "obs.prom")
    E.dump_json(reg, jpath)
    E.dump_prometheus(reg, ppath)
    with open(jpath) as f:
        snap = json.load(f)
    assert snap["counters"]["req_total"]["value"] == 42
    with open(ppath) as f:
        assert E.parse_prometheus(f.read())["req_total"] == 42.0


# ----------------------------------------------------------------------------
# Registry views through the serving tier
# ----------------------------------------------------------------------------

def test_server_stats_are_registry_views():
    from repro.core import formats as F, matgen
    from repro.launch import server as SV
    import jax.numpy as jnp

    csr = matgen.pruned_weight(256, 128, 0.05, (1, 8), seed=0)
    mat = F.csr_to_spc5(csr, 1, 8)
    reg = M.Registry()
    cache = SV.PlanCache(capacity_bytes=1 << 30, registry=reg)
    plan = cache.get_or_build(mat, layout="whole_vector", cb=64,
                              tune=False)
    srv = SV.SPC5Server(plan, cache=cache, window_us=500, max_batch=8)
    x = jnp.ones((mat.shape[1],), jnp.float32)
    with srv:
        srv.submit(x).result(timeout=60)
    # the stats() dict and the registry agree -- stats IS a registry view
    st = srv.stats()
    assert st["requests"] == reg.counter(
        "spc5_server_requests_total").value == 1
    assert st["batches"] == reg.counter(
        "spc5_server_batches_total").value >= 1
    assert cache.misses == reg.counter(
        "spc5_plan_cache_misses_total").value == 1
    assert reg.histogram("spc5_server_request_seconds").count == 1
    # the submit -> batch trace context survived the thread hop
    evs = {e.name: e for e in reg.spans()}
    assert "serve.submit" in evs and "serve.batch" in evs
    assert evs["serve.batch"].parent_id == evs["serve.submit"].span_id
    # per-plan exec stats rode on the cache entry
    assert st["plan"]["calls"] >= 1
    assert st["plan"]["gflops_achieved"] > 0
