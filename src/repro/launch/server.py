"""Persistent SpMV serving tier: plan cache, request coalescing, traffic.

The launcher in ``repro.launch.serve`` builds a plan per process and calls
it in a closed loop; this module is the persistent tier behind it, shared
by the CLI and the programmatic ``start(config)`` path:

  * :class:`ServeConfig` -- every serve knob as one frozen dataclass. The
    CLI's argparse flags are GENERATED from its fields
    (:func:`add_config_args` / :func:`config_from_args`), so a knob that
    exists on the command line provably exists on the config (the
    ``serve-config-knobs`` lint rule keeps it that way).
  * :class:`PlanCache` -- built plans keyed by
    ``plan.plan_cache_key(mat, **request)`` (matrix content fingerprint +
    the normalised prepare request), verified at admission time
    (``repro.analysis.verify``), evicted LRU by device-array footprint
    (``plan.plan_nbytes``); hit/miss/eviction counters in :meth:`stats`.
  * :class:`SPC5Server` -- request coalescing: concurrent ``submit`` calls
    gather into ONE SpMM up to the plan's tuned ``xw`` under a bounded-wait
    batching window, with the next microbatch prefetched asynchronously (a
    depth-2 handoff queue lets the gather thread stack batch k+1 while the
    executor runs batch k). Batches pad to power-of-two widths so the
    executor sees a bounded set of SpMM shapes; padding columns are zero
    and SpMM is column-independent, so coalesced results stay bit-identical
    to per-request SpMV (pinned by tests/test_server.py).
  * :func:`open_loop` / :func:`saturation_sweep` -- an open-loop traffic
    harness: Poisson arrivals at a configured QPS (submission times are
    scheduled up front and never wait on completions), per-request p50/p99
    latency (bucket-interpolated from a ``repro.obs`` histogram, not a
    sorted sample list), achieved-vs-offered QPS, swept multiplicatively
    until the tier stops keeping up. Shed/expired/failed requests are
    counted as errors, never folded into the latency distribution, so
    the tail is honest. ``benchmarks.bench_serve`` records the sweep as
    the ``spmv_serve.*`` section (and an overload point as
    ``spmv_serve_overload.*``) under the CI perf-regression gate.

The tier is built to degrade, not fall over (``repro.launch.resilience``
holds the primitives, ``repro.obs.faults`` the injection that proves it):

  * **admission control** -- ``submit`` validates the vector (shape,
    dtype, finiteness) so one poisoned request cannot fail its coalesced
    batch, sheds with :class:`~repro.launch.resilience.ShedError` once
    ``max_pending`` requests are queued, and stamps each request with an
    absolute deadline (``obs.monotonic``-based) that coalescing
    propagates: expired requests drop at gather AND again right before
    dispatch, failing with ``DeadlineExceededError`` instead of being
    computed-then-discarded.
  * **supervised workers** -- gather and exec run as
    :class:`~repro.launch.resilience.SupervisedWorker` iterations: a
    crash (injected ``serve.gather``/``serve.exec`` faults included)
    restarts the thread with bounded backoff and no request or batch is
    lost; a worker that exhausts its consecutive-crash budget latches the
    circuit breaker open, so ``submit`` fails fast with
    ``CircuitOpenError`` instead of queueing into a wedged tier.
  * **the degradation ladder** -- a failed plan build or cache admission
    retries down ``resilience.ladder_requests`` (tuned -> f32 values ->
    reference), recording each demotion as a
    ``{"pass": "degrade"}`` entry in ``plan.trace``; a failed dispatch
    retries once on the reference oracle (the non-Pallas jnp path) under
    ``faults.suppress()``, counted in ``spc5_server_degraded_total``.
    Every non-shed request either returns a correct y or fails with a
    typed error -- the chaos suite (tests/test_resilience.py) holds the
    tier to that at a 10% injected fault rate on every catalogued point.

Every counter, latency distribution, and timed region in this module is a
``repro.obs`` instrument or span: ``PlanCache``/``SPC5Server`` counters
are VIEWS over a metrics registry (``stats()`` reads the same numbers a
Prometheus export would), each cache entry carries
:class:`PlanExecStats` (calls, columns, achieved gflops vs the roofline
model for that plan's layout x value dtype), and a request's trace context
propagates ``submit`` -> coalesce window -> SpMM dispatch, and every span
is mirrored into the profiler trace ``serve.py --metrics`` records, beside
the device's ops.
"""
from __future__ import annotations

import argparse
import collections
import concurrent.futures
import contextlib
import dataclasses
import queue
import threading
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis.peaks import chip_peaks
from repro.core import formats as F
from repro.core import plan as P
from repro.launch import resilience


# ----------------------------------------------------------------------------
# ServeConfig: the one declaration of every serve knob
# ----------------------------------------------------------------------------

def _knob(default, help: str, **meta):
    meta["help"] = help
    return dataclasses.field(default=default, metadata=meta)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Every serve knob, CLI and programmatic alike.

    The field set is the source of truth: ``add_config_args`` generates one
    ``--flag`` per field (``_`` -> ``-``), and the ``serve-config-knobs``
    lint rule rejects any literal ``add_argument`` knob in the launch
    modules that does not map back to a field here.
    """

    # --- decode-loop launcher (repro.launch.serve) ---
    arch: str = _knob("yi-6b", "model architecture for the decode loop")
    batch: int = _knob(4, "decode batch size")
    tokens: int = _knob(32, "tokens to decode")
    mesh: str = _knob("", "DxM device mesh, e.g. 1x4 (empty = 1 device)")
    kv_dtype: str = _knob("bfloat16", "KV-cache dtype",
                          choices=["bfloat16", "int8"])

    # --- sparse-layer build inputs ---
    records: str = _knob("", "SPC5 record store (file or dir) for "
                             "auto-tuned sparse-layer configs")
    vocab_spmv: float = _knob(0.0, "bench/serve a pruned vocab projection "
                                   "at this density (0 = off)",
                              metavar="DENSITY")
    panel: str = _knob("", "explicit pr,xw,cb (overrides the tuned config)")
    reorder: str = _knob("", "reordering strategy (sigma, rcm, colwindow, "
                             "auto; empty = none)")
    vdtype: str = _knob("auto", "stored value dtype for the sparse layer "
                                "(quantised stores accumulate in f32)",
                        choices=["auto", "f32", "bf16", "int8"])
    verify: bool = _knob(False, "statically verify records on load and "
                                "every plan at cache-admission time")

    # --- serving tier ---
    cache_mb: int = _knob(256, "plan-cache capacity in MiB (LRU by plan "
                               "device-array bytes)")
    window_us: float = _knob(200.0, "coalescing bounded-wait window in "
                                    "microseconds")
    max_batch: int = _knob(0, "coalescing cap (0 = the plan's tuned xw)")
    prefetch_depth: int = _knob(2, "microbatches stacked ahead of the "
                                   "executor")
    qps: float = _knob(0.0, "open-loop Poisson arrival rate; with "
                            "--vocab-spmv routes the bench through the "
                            "serving tier (0 = closed-loop microbench)")
    duration_s: float = _knob(0.5, "open-loop bench duration per QPS point")

    # --- resilience (repro.launch.resilience / repro.obs.faults) ---
    max_pending: int = _knob(1024, "admission-control bound on queued "
                                   "requests; submit sheds beyond it "
                                   "(0 = unbounded)")
    deadline_ms: float = _knob(0.0, "per-request deadline in milliseconds; "
                                    "expired requests drop before dispatch "
                                    "(0 = none)")
    faults: str = _knob("", "arm fault injection: point:rate[:seed],... "
                            "over repro.obs.faults.CATALOGUE (chaos runs; "
                            "same spec as SPC5_FAULTS)")
    no_degrade: bool = _knob(False, "disable the graceful-degradation "
                                    "ladder: fail a broken build/dispatch "
                                    "instead of demoting down the lattice")

    # --- observability (repro.obs) ---
    metrics: bool = _knob(False, "record serve metrics/spans on the global "
                                 "obs registry and export them at exit")
    metrics_path: str = _knob("serve_metrics.prom", "Prometheus text "
                              "snapshot path (with --metrics)")
    trace_path: str = _knob("serve_trace", "jax.profiler trace directory "
                            "(with --metrics): obs spans and device ops "
                            "on one clock")


def add_config_args(ap: argparse.ArgumentParser,
                    cls=ServeConfig) -> argparse.ArgumentParser:
    """Generate one ``--flag`` per ``cls`` field (the only argparse source
    for serve knobs; bools become ``store_true`` switches)."""
    for f in dataclasses.fields(cls):
        flag = "--" + f.name.replace("_", "-")
        meta = dict(f.metadata)
        if isinstance(f.default, bool):
            ap.add_argument(flag, action="store_true",
                            help=meta.get("help"))
        else:
            ap.add_argument(flag, type=type(f.default), default=f.default,
                            **meta)
    return ap


def config_from_args(args: argparse.Namespace, cls=ServeConfig):
    """The parsed-namespace -> config half of the argparse round trip."""
    return cls(**{f.name: getattr(args, f.name)
                  for f in dataclasses.fields(cls)})


def plan_request(config: ServeConfig) -> Dict[str, object]:
    """The ``ops.prepare`` keyword request a config describes -- also the
    cache-key payload (``plan.plan_cache_key`` normalises the defaults)."""
    req: Dict[str, object] = {"vdtype": config.vdtype}
    if config.panel:
        pr, xw, cb = (int(v) for v in config.panel.split(","))
        req.update(layout="panels", pr=pr, xw=xw, cb=cb, tune=False)
    if config.reorder:
        req["reorder"] = config.reorder
    return req


# ----------------------------------------------------------------------------
# PlanCache: fingerprint-keyed, verify-on-admission, LRU by plan bytes
# ----------------------------------------------------------------------------

class PlanExecStats:
    """Per-plan execution stats, recorded on the cache entry: how many
    dispatches this plan served, how many request columns they carried,
    and the achieved gflops against the roofline ceiling for THIS plan's
    value dtype (``formats.spmv_bytes_per_nnz`` at the plan's measured avg
    nnz/block and its ACTUAL value itemsize, x the HBM bandwidth of
    ``device_kind``, by default the first JAX device's). A device without
    a published peak (``repro.analysis.peaks``) gets no ceiling:
    ``gflops_roofline`` and ``roofline_fraction`` are None."""

    def __init__(self, plan: P.SPC5Plan, device_kind: Optional[str] = None):
        meta = dict(plan.meta)
        self.nnz = int(meta.get("nnz") or 0)
        self._lock = threading.Lock()
        self.calls = 0
        self.columns = 0
        self.seconds = 0.0
        self.gflops_roofline: Optional[float] = None
        if device_kind is None:
            device_kind = jax.devices()[0].device_kind
        chip = chip_peaks(device_kind)
        r, c, nblocks = meta.get("r"), meta.get("c"), meta.get("nblocks")
        if chip and self.nnz and r and c and nblocks:
            # quantised plans move fewer value bytes: the ceiling rises
            bpn = F.spmv_bytes_per_nnz(
                int(r), int(c), self.nnz / nblocks,
                s_float=F.value_itemsize(meta.get("vdtype") or ""))
            self.gflops_roofline = 2.0 / bpn * chip["hbm_bytes_per_s"] / 1e9

    def record(self, ncols: int, seconds: float) -> None:
        with self._lock:
            self.calls += 1
            self.columns += int(ncols)
            self.seconds += seconds

    @property
    def gflops_achieved(self) -> float:
        return (2.0 * self.nnz * self.columns / self.seconds / 1e9
                if self.seconds > 0 else 0.0)

    def as_dict(self) -> Dict[str, float]:
        ach = self.gflops_achieved
        return {"calls": self.calls, "columns": self.columns,
                "seconds": self.seconds, "gflops_achieved": ach,
                "gflops_roofline": self.gflops_roofline,
                "roofline_fraction": (ach / self.gflops_roofline
                                      if self.gflops_roofline else None)}


class PlanCache:
    """Built plans keyed by (matrix fingerprint, normalised request).

    ``get_or_build`` hashes the matrix CONTENT (``plan.matrix_fingerprint``)
    plus every requested build decision, so a re-uploaded but identical
    matrix hits while one flipped mask bit or a different layout misses.
    Admission optionally proves the fresh plan's format/plan invariants
    (``repro.analysis.verify``) before it can serve a request; eviction is
    LRU by device-array footprint (``plan.plan_nbytes``) against
    ``capacity_bytes``. Thread-safe: the serving tier builds from its
    gather thread while callers warm plans from theirs.

    The hit/miss/eviction counters are ``repro.obs`` counters on
    ``registry`` (a private registry per cache by default, so
    test-constructed caches never share totals); ``hits``/``misses``/
    ``evictions`` remain as read-only views and ``stats()`` reads the
    registry. Each entry carries a :class:`PlanExecStats` the serving
    tier feeds per dispatch (``stats_for``).

    With ``degrade=True`` (the default) a failed build or admission --
    a builder exception, a verify rejection, an injected ``plan.build``
    or ``cache.admit`` fault -- retries down
    :func:`resilience.ladder_requests`; the plan the ladder lands on is
    cached under the ORIGINAL request's key (the caller asked for y =
    A @ x, not for a particular plan) with each demotion appended to
    ``plan.trace`` as a ``{"pass": "degrade"}`` entry and counted in
    ``spc5_plan_cache_degraded_total``.
    """

    def __init__(self, capacity_bytes: int = 256 << 20, *,
                 verify_on_admit: bool = False,
                 builder: Optional[Callable[..., P.SPC5Plan]] = None,
                 registry: Optional[obs.Registry] = None,
                 degrade: bool = True):
        self.capacity_bytes = int(capacity_bytes)
        self.verify_on_admit = verify_on_admit
        self.degrade = degrade
        if builder is None:
            from repro.kernels import ops
            builder = ops.prepare
        self._build = builder
        self._entries: "collections.OrderedDict[str, tuple]" = \
            collections.OrderedDict()   # key -> (plan, nbytes, PlanExecStats)
        self._bytes = 0
        self._lock = threading.Lock()
        self.registry = registry if registry is not None else obs.Registry()
        self._hits = self.registry.counter(
            "spc5_plan_cache_hits_total", "plan-cache hits")
        self._misses = self.registry.counter(
            "spc5_plan_cache_misses_total", "plan-cache misses")
        self._evictions = self.registry.counter(
            "spc5_plan_cache_evictions_total", "plan-cache LRU evictions")
        self._degraded = self.registry.counter(
            "spc5_plan_cache_degraded_total",
            "builds served by a degradation-ladder rung")
        self._build_seconds = self.registry.histogram(
            "spc5_plan_cache_build_seconds", "cold plan-build wall time")

    # counters are views over the registry, never writable ints
    @property
    def hits(self) -> int:
        return self._hits.value

    @property
    def misses(self) -> int:
        return self._misses.value

    @property
    def evictions(self) -> int:
        return self._evictions.value

    def _build_attempt(self, mat: F.SPC5Matrix, request: Dict[str, object],
                       *, suppress: bool = False) -> P.SPC5Plan:
        """One ladder rung: build, verify (when configured), admit. The
        injected ``cache.admit`` fault fires AFTER a successful build,
        exactly where a verify rejection would surface; the reference
        rung runs with injection suppressed on this thread."""
        faults = obs.faults.get_faults()
        with faults.suppress() if suppress else contextlib.nullcontext():
            plan = self._build(mat, **request)
            if self.verify_on_admit:
                from repro.analysis.verify import verify_plan
                verify_plan(plan).raise_if_failed()
            faults.maybe_fail("cache.admit")
        return plan

    def _admit(self, mat: F.SPC5Matrix,
               request: Dict[str, object]) -> P.SPC5Plan:
        """Build the requested plan, demoting down the ladder on failure
        (when ``degrade``); raises the LAST rung's error if every rung
        fails. The returned plan's trace carries one ``degrade`` entry
        per rung tried, so "which rung served this" is auditable."""
        try:
            return self._build_attempt(mat, request)
        except Exception as e:      # noqa: BLE001 -- ladder entry point
            if not self.degrade:
                raise
            last: Exception = e
        entries: List[dict] = []
        for rung, req, suppress in resilience.ladder_requests(request):
            with self.registry.span("cache.degrade", rung=rung) as sp:
                try:
                    plan = self._build_attempt(mat, req, suppress=suppress)
                    err = None
                except Exception as e:  # noqa: BLE001 -- try the next rung
                    err = e
            entries.append({"pass": "degrade", "rung": rung,
                            "reason": f"{type(last).__name__}: {last}",
                            "duration_s": sp.duration_s})
            if err is None:
                self._degraded.inc()
                return P.append_trace_entries(plan, entries)
            last = err
        raise last

    def get_or_build(self, mat: F.SPC5Matrix, **request) -> P.SPC5Plan:
        key = P.plan_cache_key(mat, **request)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self._hits.inc()
                return hit[0]
            self._misses.inc()
        # build outside the lock: a slow build must not serialise hits
        with self.registry.span("cache.build") as sp:
            plan = self._admit(mat, request)
        self._build_seconds.observe(sp.duration_s)
        nbytes = P.plan_nbytes(plan)
        with self._lock:
            if key not in self._entries:
                while self._entries and self._bytes + nbytes > \
                        self.capacity_bytes:
                    _, (_, old, _) = self._entries.popitem(last=False)
                    self._bytes -= old
                    self._evictions.inc()
                self._entries[key] = (plan, nbytes, PlanExecStats(plan))
                self._bytes += nbytes
        return plan

    def stats_for(self, plan: P.SPC5Plan) -> PlanExecStats:
        """The exec-stats slot for a cached plan (by identity); plans the
        cache no longer holds get a fresh, unattached slot."""
        with self._lock:
            for p, _, st in self._entries.values():
                if p is plan:
                    return st
        return PlanExecStats(plan)

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> Dict[str, float]:
        total = self.hits + self.misses
        out = {"hits": self.hits, "misses": self.misses,
               "evictions": self.evictions,
               "degraded": self._degraded.value,
               "entries": len(self._entries),
               "bytes": self._bytes, "capacity_bytes": self.capacity_bytes,
               "hit_rate": self.hits / total if total else 0.0}
        with self._lock:
            out["plans"] = [dict(st.as_dict(), layout=p.layout)
                            for p, _, st in self._entries.values()]
        return out


# ----------------------------------------------------------------------------
# SPC5Server: bounded-wait coalescing with async microbatch prefetch
# ----------------------------------------------------------------------------

#: ``ctx`` is the submit span's id: the exec thread opens its batch span
#: with ``parent=ctx`` so the cross-thread request lifetime is one trace.
#: ``deadline`` is an ABSOLUTE ``obs.monotonic`` time (or None): it rides
#: with the request through coalescing, so expired requests drop at
#: gather and again right before dispatch, never computed-then-discarded.
_Request = collections.namedtuple("_Request", "x future t_submit deadline ctx")


def _pow2_width(n: int, cap: int) -> int:
    """Batches pad to power-of-two widths (capped at the coalescing limit)
    so the executor sees a bounded set of SpMM shapes."""
    w = 1
    while w < n:
        w <<= 1
    return min(w, max(cap, n))


class SPC5Server:
    """Coalesce concurrent SpMV requests into one SpMM.

    ``submit(x)`` enqueues a vector and returns a future. A gather thread
    drains the queue into microbatches: it takes the first waiter, then
    holds the batch open for at most ``window_us`` (the bounded-wait
    window) or until ``max_batch`` columns -- the plan's tuned ``xw`` by
    default, so a full batch is exactly the column tile the kernel was
    tuned for. Finished batches land on a depth-``prefetch_depth`` handoff
    queue; while the executor runs batch k, the gather thread is already
    stacking batch k+1 (the async prefetch). A single-request batch runs
    the SpMV executor; a wider one pads to the next power of two with zero
    columns and runs SpMM -- column-independent, so every caller's y is
    bit-identical to a lone ``execute_spmv`` (see tests/test_server.py).

    Both threads are :class:`resilience.SupervisedWorker` iterations (a
    crash restarts the worker, losing no request: the ``serve.gather`` /
    ``serve.exec`` fault points fire BEFORE any request or batch is taken
    off its queue); ``submit`` is the admission-control gate (validation,
    ``max_pending`` shedding, deadlines, circuit breaker) and a failed
    dispatch retries once on the reference oracle under
    ``faults.suppress()`` before failing its callers. See the module
    docstring for the full resilience contract.
    """

    def __init__(self, plan: P.SPC5Plan, *, cache: Optional[PlanCache] = None,
                 window_us: float = 200.0, max_batch: int = 0,
                 prefetch_depth: int = 2,
                 registry: Optional[obs.Registry] = None,
                 max_pending: int = 1024, deadline_s: float = 0.0,
                 degrade: bool = True, max_restarts: int = 8,
                 breaker_threshold: int = 8, breaker_reset_s: float = 0.5):
        self.plan = plan
        self.cache = cache
        meta = dict(plan.meta)
        self.max_batch = int(max_batch) if max_batch and max_batch > 0 \
            else int(meta.get("xw") or 128)
        self.window_s = float(window_us) * 1e-6
        self.max_pending = max(0, int(max_pending))
        self.deadline_s = float(deadline_s)
        self.degrade = degrade
        self._ncols = int(meta.get("ncols") or 0)
        self._pending: "collections.deque[_Request]" = collections.deque()
        self._cv = threading.Condition()
        self._closed = False
        self._batches: "queue.Queue" = queue.Queue(maxsize=max(
            1, int(prefetch_depth)))
        # instruments live on the cache's registry when one is attached
        # (one scrape covers the whole tier), else a private registry
        self.registry = registry if registry is not None else (
            cache.registry if cache is not None else obs.Registry())
        self._requests = self.registry.counter(
            "spc5_server_requests_total", "requests submitted")
        self._batches_total = self.registry.counter(
            "spc5_server_batches_total", "coalesced batches executed")
        self._coalesced = self.registry.counter(
            "spc5_server_coalesced_total",
            "requests that shared a multi-request batch")
        self._widest = self.registry.gauge(
            "spc5_server_widest_batch", "widest batch coalesced so far")
        self._batch_seconds = self.registry.histogram(
            "spc5_server_batch_seconds", "batch dispatch-to-ready time")
        self._request_seconds = self.registry.histogram(
            "spc5_server_request_seconds", "submit-to-result latency")
        self._shed = self.registry.counter(
            "spc5_server_shed_total",
            "requests shed by admission control (pending bound)")
        self._expired = self.registry.counter(
            "spc5_server_expired_total",
            "requests dropped because their deadline passed before "
            "dispatch")
        self._invalid = self.registry.counter(
            "spc5_server_invalid_total",
            "requests rejected by submit-time validation")
        self._degraded = self.registry.counter(
            "spc5_server_degraded_total",
            "batches served by the reference-oracle ladder rung")
        self._restarts = self.registry.counter(
            "spc5_server_worker_restarts_total",
            "supervised worker crash-restarts")
        self._plan_stats = (cache.stats_for(plan) if cache is not None
                            else PlanExecStats(plan))
        self._breaker = resilience.CircuitBreaker(
            threshold=breaker_threshold, reset_s=breaker_reset_s)
        # exec first: the gather handoff checks the exec worker's
        # liveness before blocking on a full prefetch queue
        self._exec_worker = resilience.SupervisedWorker(
            "spc5-exec", self._exec_once, restarts=self._restarts,
            max_restarts=max_restarts,
            on_give_up=self._on_worker_give_up).start()
        self._gather_worker = resilience.SupervisedWorker(
            "spc5-gather", self._gather_once, restarts=self._restarts,
            max_restarts=max_restarts,
            on_give_up=self._on_worker_give_up).start()

    def _faults_now(self):
        """The process-global fault registry, resolved per call so a test
        arming ``set_faults`` after construction still injects here."""
        return obs.faults.get_faults()

    # -- client API ----------------------------------------------------------

    def _validate(self, x) -> jax.Array:
        """Admission validation: shape, dtype, finiteness. A poisoned
        vector fails HERE, alone, with :class:`ValueError` -- never
        inside a coalesced batch where it would fail every rider."""
        xv = jnp.asarray(x)
        ok = (xv.ndim == 1
              and (self._ncols == 0 or int(xv.shape[0]) == self._ncols)
              and jnp.issubdtype(xv.dtype, jnp.floating))
        if ok and not bool(jnp.all(jnp.isfinite(xv))):
            ok = False
            why = "contains non-finite values (NaN/Inf)"
        elif not ok:
            why = (f"must be a 1-D floating vector of length "
                   f"{self._ncols or 'ncols'}, got shape "
                   f"{tuple(xv.shape)} dtype {xv.dtype}")
        if not ok:
            self._invalid.inc()
            raise ValueError(f"invalid request vector: {why}")
        return xv

    def submit(self, x, *,
               deadline_s: Optional[float] = None
               ) -> "concurrent.futures.Future":
        """Enqueue y = A @ x; the future resolves to y (original row
        order, device-ready).

        The admission-control gate, in order: :class:`CircuitOpenError`
        when the breaker is open (a worker gave up / the executor keeps
        failing), :class:`ValueError` for an invalid vector,
        ``RuntimeError`` after :meth:`close`, :class:`ShedError` once
        ``max_pending`` requests are queued. ``deadline_s`` (relative,
        seconds; default the server's ``deadline_s``) stamps the request
        with an absolute expiry the coalescing pipeline honours.
        """
        if not self._breaker.allow():
            raise resilience.CircuitOpenError(
                "circuit open: the serving tier is failing; submit "
                "rejected fast instead of queueing into a wedged tier")
        xv = self._validate(x)
        dl = self.deadline_s if deadline_s is None else float(deadline_s)
        with self.registry.span("serve.submit") as sp:
            now = obs.monotonic()
            req = _Request(xv, concurrent.futures.Future(), now,
                           now + dl if dl > 0 else None, sp.span_id)
            # closed-check and append under ONE lock: submit can never
            # slip a request into a server that is concurrently closing
            with self._cv:
                if self._closed:
                    raise RuntimeError("server is closed")
                if self.max_pending and \
                        len(self._pending) >= self.max_pending:
                    self._shed.inc()
                    raise resilience.ShedError(
                        f"pending queue at its admission bound "
                        f"({self.max_pending}); request shed")
                self._pending.append(req)
                self._cv.notify_all()
        return req.future

    def spmv(self, x, timeout: Optional[float] = None) -> jax.Array:
        """Synchronous y = A @ x through the coalescing path."""
        return self.submit(x).result(timeout=timeout)

    def close(self, timeout: float = 5.0) -> None:
        """Stop admitting, drain what is queued, join both workers, and
        resolve EVERY outstanding future: whatever the drain did not
        serve is cancelled (``concurrent.futures.CancelledError`` for
        waiters), never silently abandoned. Raises ``RuntimeError`` if a
        worker is still running after its ``timeout`` join -- a hung
        close must be loud, not a leaked thread."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        stuck = [w.name for w in (self._gather_worker, self._exec_worker)
                 if not w.join(timeout)]
        with self._cv:
            leftovers = list(self._pending)
            self._pending.clear()
        while True:
            try:
                leftovers.extend(self._batches.get_nowait())
            except queue.Empty:
                break
        for r in leftovers:
            # cancel() alone leaves the future CANCELLED but un-notified:
            # callers blocked in concurrent.futures.wait() would sleep
            # forever. The notify step completes the transition.
            if r.future.cancel():
                r.future.set_running_or_notify_cancel()
        if stuck:
            raise RuntimeError(
                f"SPC5Server.close: worker(s) {stuck} still running "
                f"after a {timeout}s join; outstanding futures were "
                f"cancelled")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- registry views ------------------------------------------------------

    @property
    def requests(self) -> int:
        return self._requests.value

    @property
    def batches(self) -> int:
        return self._batches_total.value

    @property
    def widest_batch(self) -> int:
        return int(self._widest.value)

    def stats(self) -> Dict[str, object]:
        """Every number here is a view over ``self.registry`` -- the same
        instruments a Prometheus export or ``obs.snapshot`` reads."""
        out: Dict[str, object] = {
            "requests": self.requests, "batches": self.batches,
            "mean_batch": (self.requests / self.batches
                           if self.batches else 0.0),
            "widest_batch": self.widest_batch,
            "coalesced": self._coalesced.value,
            "shed": self._shed.value,
            "expired": self._expired.value,
            "invalid": self._invalid.value,
            "degraded": self._degraded.value,
            "worker_restarts": self._restarts.value,
            "breaker": self._breaker.state,
            "max_pending": self.max_pending,
            "max_batch": self.max_batch,
            "window_us": self.window_s * 1e6,
            "p50_us": self._request_seconds.percentile(50) * 1e6,
            "p99_us": self._request_seconds.percentile(99) * 1e6,
            "plan": self._plan_stats.as_dict(),
        }
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        return out

    # -- supervised worker iterations ----------------------------------------

    @staticmethod
    def _fail_reqs(reqs: Sequence[_Request], exc: BaseException) -> None:
        for r in reqs:
            if not r.future.done():
                r.future.set_exception(exc)

    def _drop_expired(self, reqs: List[_Request]) -> List[_Request]:
        """Fail requests whose deadline passed; keep the live ones. Runs
        at gather (post-window) and again right before dispatch, so an
        expired request is never computed-then-discarded."""
        now = obs.monotonic()
        keep: List[_Request] = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                self._expired.inc()
                if not r.future.done():
                    r.future.set_exception(resilience.DeadlineExceededError(
                        f"deadline exceeded {(now - r.deadline) * 1e3:.2f}"
                        f"ms before dispatch"))
            else:
                keep.append(r)
        return keep

    def _on_worker_give_up(self, exc: BaseException) -> None:
        """A worker exhausted its consecutive-crash budget: the tier is
        wedged. Latch the breaker open (submit fails fast from now on)
        and fail everything already queued -- no caller is left holding
        a future nobody will ever resolve."""
        self._breaker.force_open()
        with self._cv:
            orphans = list(self._pending)
            self._pending.clear()
        err = resilience.CircuitOpenError(
            f"serving tier wedged: a worker gave up after repeated "
            f"crashes ({type(exc).__name__}: {exc})")
        self._fail_reqs(orphans, err)
        while True:
            try:
                self._fail_reqs(self._batches.get_nowait(), err)
            except queue.Empty:
                break

    def _handoff(self, reqs: List[_Request]) -> None:
        """Put a batch on the prefetch queue without deadlocking against
        a dead executor: the bounded put re-checks exec liveness."""
        while True:
            if self._exec_worker.done:
                self._fail_reqs(reqs, resilience.CircuitOpenError(
                    "executor worker is gone; batch dropped"))
                return
            try:
                self._batches.put(reqs, timeout=0.05)
                return
            except queue.Full:
                continue

    def _gather_once(self):
        """One gather iteration: coalesce a microbatch and hand it off.
        The ``serve.gather`` fault fires FIRST -- before any request is
        popped -- so an injected gather crash loses nothing; the
        supervisor restarts the worker and the queue drains next pass."""
        self._faults_now().maybe_fail("serve.gather")
        with self._cv:
            if not self._pending:
                if self._closed:
                    return resilience.DONE
                self._cv.wait(timeout=0.05)
                if not self._pending:
                    return None     # short iterations: crisp supervision
            reqs = [self._pending.popleft()]
            deadline = obs.monotonic() + self.window_s
            while len(reqs) < self.max_batch:
                if self._pending:
                    reqs.append(self._pending.popleft())
                    continue
                remaining = deadline - obs.monotonic()
                if remaining <= 0 or self._closed:
                    break
                self._cv.wait(timeout=remaining)
        reqs = self._drop_expired(reqs)
        if reqs:
            self._handoff(reqs)
        return None

    def _run_batch(self, reqs: List[_Request],
                   oracle: bool = False) -> List[jax.Array]:
        """Dispatch one coalesced batch; ``oracle=True`` is the ladder's
        last rung -- the layout's non-Pallas jnp reference path."""
        kw = dict(use_pallas=False) if oracle else {}
        if len(reqs) == 1:
            y = P.execute_spmv(self.plan, reqs[0].x, **kw)
            jax.block_until_ready(y)
            return [y]
        width = _pow2_width(len(reqs), self.max_batch)
        X = jnp.stack([r.x for r in reqs], axis=1)
        if width > len(reqs):
            pad = jnp.zeros((X.shape[0], width - len(reqs)), X.dtype)
            X = jnp.concatenate([X, pad], axis=1)
        Y = P.execute_spmm(self.plan, X, **kw)
        jax.block_until_ready(Y)
        return [Y[:, j] for j in range(len(reqs))]

    def _exec_once(self):
        """One executor iteration: take a batch, dispatch it, resolve its
        futures. The ``serve.exec`` fault fires BEFORE the queue take,
        so an injected executor crash loses no batch. A failed dispatch
        retries once on the reference oracle under ``faults.suppress()``
        (the exec-side degradation ladder); only a rung-exhausted batch
        fails its callers, and THAT feeds the circuit breaker."""
        self._faults_now().maybe_fail("serve.exec")
        try:
            reqs = self._batches.get(timeout=0.05)
        except queue.Empty:
            gather = getattr(self, "_gather_worker", None)
            if self._closed and gather is not None and gather.done \
                    and self._batches.empty():
                return resilience.DONE
            return None
        reqs = self._drop_expired(reqs)
        if not reqs:
            return None
        try:
            # the batch span parents on the FIRST request's submit span:
            # submit -> coalesce window -> dispatch is one trace
            with self.registry.span("serve.batch", parent=reqs[0].ctx,
                                    n=len(reqs)) as sp:
                try:
                    ys = self._run_batch(reqs)
                except Exception:
                    if not self.degrade:
                        raise
                    # one rung down: the reference oracle, injection
                    # suppressed on this thread so the rung the ladder
                    # lands on cannot be re-failed by the chaos it is
                    # recovering from
                    with self._faults_now().suppress():
                        ys = self._run_batch(reqs, oracle=True)
                    self._degraded.inc()
            self._batches_total.inc()
            self._requests.inc(len(reqs))
            self._widest.set_max(len(reqs))
            if len(reqs) > 1:
                self._coalesced.inc(len(reqs))
            self._batch_seconds.observe(sp.duration_s)
            self._plan_stats.record(len(reqs), sp.duration_s)
            done = obs.monotonic()
            for r, y in zip(reqs, ys):
                self._request_seconds.observe(done - r.t_submit)
                if not r.future.done():
                    r.future.set_result(y)
            self._breaker.record_success()
        except Exception as e:      # noqa: BLE001 -- fail the callers
            self._breaker.record_failure()
            self._fail_reqs(reqs, e)
        return None


# ----------------------------------------------------------------------------
# Open-loop traffic harness
# ----------------------------------------------------------------------------

def open_loop(server: SPC5Server, xs: Sequence, qps: float,
              duration_s: float = 0.5, seed: int = 0,
              warmup: int = 2) -> Dict[str, float]:
    """Drive ``server`` open-loop: Poisson arrivals at ``qps`` for
    ``duration_s``, submissions never waiting on completions.

    Arrival times are drawn up front (exponential inter-arrivals); each
    request's latency is submit-to-future-resolution, measured by a done
    callback so the driver thread never sits in ``result()``. Latencies
    land in a fresh ``repro.obs`` histogram (one per call, so QPS points
    never mix) and p50/p99 come from bucket interpolation -- O(buckets)
    memory instead of the old O(requests) sorted list, with the bounded
    bucket-ratio error tests/test_obs.py pins.

    Only SUCCESSFUL requests enter the latency histogram and the
    achieved-QPS numerator; shed, expired, failed, cancelled, and
    timed-out requests are counted in ``shed``/``expired``/``errors``
    (an early version folded failures into the latency distribution,
    which made an overloaded tier's tail look BETTER as it dropped more
    work). The gap between offered and achieved QPS is the saturation
    signal (:func:`saturation_sweep`); the shed rate at 2x the
    saturation QPS is the overload signal
    (``benchmarks.bench_serve.overload``).
    """
    import time as _time    # sleep only; timestamps come from obs
    rng = np.random.default_rng(seed)
    for i in range(warmup):
        try:
            server.spmv(xs[i % len(xs)])
        except Exception:   # noqa: BLE001 -- warmup under chaos may fail
            pass
    arrivals, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / qps)
        if t >= duration_s:
            break
        arrivals.append(t)
    if not arrivals:
        arrivals = [0.0]
    hist = obs.Histogram("open_loop_latency_seconds")
    counts = collections.Counter()
    counts_lock = threading.Lock()

    def _record(t_submit, fut):
        # classify BEFORE observing: a failed request has no honest
        # latency, only an error count
        if fut.cancelled():
            kind = "cancelled"
        else:
            exc = fut.exception()
            if exc is None:
                hist.observe(obs.monotonic() - t_submit)
                return
            kind = ("expired"
                    if isinstance(exc, resilience.DeadlineExceededError)
                    else "failed")
        with counts_lock:
            counts[kind] += 1

    t0 = obs.monotonic()
    futures, submitted = [], 0
    for t in arrivals:
        delay = t0 + t - obs.monotonic()
        if delay > 0:
            _time.sleep(delay)
        ts = obs.monotonic()
        submitted += 1
        try:
            fut = server.submit(xs[submitted % len(xs)])
        except resilience.ShedError:
            with counts_lock:
                counts["shed"] += 1
            continue
        except Exception:   # noqa: BLE001 -- breaker open, closed, ...
            with counts_lock:
                counts["rejected"] += 1
            continue
        fut.add_done_callback(lambda f, ts=ts: _record(ts, f))
        futures.append(fut)
    # bounded wait: an unresolved future is a timeout error, not a hang
    not_done = concurrent.futures.wait(
        futures, timeout=max(5.0, 4.0 * duration_s)).not_done
    with counts_lock:
        counts["timed_out"] += len(not_done)
    elapsed = obs.monotonic() - t0
    completed = hist.count      # one snapshot: a straggler resolving
    # after the bounded wait stays a timeout, not a late success
    errors = (counts["failed"] + counts["cancelled"] + counts["rejected"]
              + counts["timed_out"])
    return {
        "qps_offered": qps,
        "qps_achieved": completed / elapsed,
        "submitted": submitted,
        "completed": completed,
        "shed": counts["shed"],
        "expired": counts["expired"],
        "errors": errors,
        "elapsed_s": elapsed,
        "p50_us": hist.percentile(50) * 1e6,
        "p99_us": hist.percentile(99) * 1e6,
    }


def saturation_sweep(server: SPC5Server, xs: Sequence, *,
                     qps0: float = 50.0, factor: float = 2.0,
                     max_points: int = 5, duration_s: float = 0.5,
                     seed: int = 0) -> List[Dict[str, float]]:
    """Sweep offered QPS multiplicatively until the tier stops keeping up
    (achieved < 85% of offered) or ``max_points`` is reached; the last
    point's achieved QPS is the saturation throughput."""
    points, qps = [], qps0
    for _ in range(max_points):
        res = open_loop(server, xs, qps, duration_s=duration_s, seed=seed)
        points.append(res)
        if res["qps_achieved"] < 0.85 * res["qps_offered"]:
            break
        qps *= factor
    return points


# ----------------------------------------------------------------------------
# start(config): the programmatic entry point the CLI shares
# ----------------------------------------------------------------------------

def _default_matrix(config: ServeConfig) -> F.SPC5Matrix:
    """The config's pruned vocab-projection matrix (the CLI's serve
    subject) at the architecture's decode shape."""
    if config.vocab_spmv <= 0:
        raise ValueError("start(config) needs a matrix: pass mat= or set "
                         "vocab_spmv > 0")
    from repro.configs import get_smoke_config
    from repro.core import matgen
    cfg = get_smoke_config(config.arch)
    csr = matgen.pruned_weight(cfg.vocab, cfg.d_model, config.vocab_spmv,
                               (1, 8), seed=0)
    return F.csr_to_spc5(csr, 1, 8)


def start(config: ServeConfig, mat: Optional[F.SPC5Matrix] = None, *,
          cache: Optional[PlanCache] = None,
          install_records: bool = True) -> SPC5Server:
    """Build the serving tier a config describes and return the running
    server: record store installed (unless the launcher already did --
    ``install_records=False``), plan built through the cache (admission
    verify when ``config.verify``), coalescing threads started.

    With ``config.metrics`` the tier's instruments and spans land on the
    GLOBAL obs registry (``obs.get_registry()``) so the CLI can export
    one Prometheus snapshot at exit (its spans are in the profiler trace
    the CLI records); otherwise the tier
    gets a private registry and leaves the global one untouched.

    ``config.faults`` arms the PROCESS-global fault registry (the same
    spec grammar as ``SPC5_FAULTS``): every wired point -- plan build,
    cache admission, kernel dispatch, both server workers -- injects for
    this tier and anything else the process runs, which is exactly what
    a chaos run wants."""
    if config.faults:
        obs.faults.set_faults(obs.faults.Faults(config.faults))
    if install_records and config.records:
        from repro.core import selector as S
        store = S.load_records(config.records)
        if config.verify:
            from repro.analysis.verify import verify_records
            verify_records(store).raise_if_failed()
        S.set_default_store(store)
    if mat is None:
        mat = _default_matrix(config)
    registry = obs.get_registry() if config.metrics else None
    if cache is None:
        cache = PlanCache(capacity_bytes=config.cache_mb << 20,
                          verify_on_admit=config.verify,
                          registry=registry,
                          degrade=not config.no_degrade)
    plan = cache.get_or_build(mat, **plan_request(config))
    return SPC5Server(plan, cache=cache, window_us=config.window_us,
                      max_batch=config.max_batch,
                      prefetch_depth=config.prefetch_depth,
                      registry=registry,
                      max_pending=config.max_pending,
                      deadline_s=config.deadline_ms * 1e-3,
                      degrade=not config.no_degrade)
