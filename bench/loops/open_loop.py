"""An open-loop stream of single-vector requests into ``SPC5Server``.

The config's matrix is served behind ``launch.server.start`` with the
config's ``serve`` settings. Arrival times are drawn up front from the
seed (``bench.openloop.arrivals`` at the mix's ``rate_per_s``), as are the
request vectors. A few submitter threads, standing for independent users,
each take the next request, sleep until it is due and submit it; a
blocking ``submit`` therefore delays only that user. Each answer is timed
when it is ready on the device, from when its request was due.

Requests still unanswered when the last one has been submitted are
drained (waited for, up to ``drain_s``); they count as attempted. A
request fails when it is shed, expires, errors or never answers.

What is compared: every answer, against the reference of its own request
vector, so a wrong row, a wrong column of a batch or an answer handed to
the wrong request all show.
"""
from __future__ import annotations

import queue
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from bench import openloop


def setup(run):
    from repro.core import formats as F
    from repro.launch import server as SV
    cfg = run.config
    shape, rowptr, colidx, values = run.generate()
    csr = F.CSRMatrix(tuple(shape), rowptr, colidx, values)
    with run.span("bench.convert", key="convert_s"):
        mat = F.csr_to_spc5(csr, *cfg["block"])
    server = SV.start(SV.ServeConfig(vdtype=cfg["vdtype"], **cfg["serve"]),
                      mat=mat)
    del mat
    plan = server.plan
    run.layer["plan_trace"] = plan.trace
    run.layer["plan"] = {"layout": plan.layout, "lowering": plan.lowering,
                         "npanels": plan.npanels, "nchunks": plan.nchunks}
    # warm every batch width the coalescer can form, through the server's
    # own batch path, then one request through submit
    warm = list(jax.device_put(
        np.ones((server.max_batch, shape[1]), np.float32), run.devices[0]))
    for n in range(1, server.max_batch + 1):
        reqs = [types.SimpleNamespace(x=x) for x in warm[:n]]
        jax.block_until_ready(server._run_batch(reqs))
    jax.block_until_ready(server.submit(warm[0]).result(timeout=600))
    state = {"server": server, "csr": (shape, rowptr, colidx, values)}
    requests(run, state)
    return state


def requests(run, state):
    """Draw the window's due times and request vectors from the seed."""
    shape = state["csr"][0]
    due = openloop.arrivals(float(run.traffic["rate_per_s"]), run.seconds,
                            run.seed)
    rng = np.random.default_rng([run.seed, 1])
    xs_host = rng.standard_normal((due.shape[0], shape[1]), dtype=np.float32)
    state.update(due=due, xs_host=xs_host,
                 xs=list(jax.device_put(xs_host, run.devices[0])))


def window(run, state):
    server, due, xs = state["server"], state["due"], state["xs"]
    tr = run.traffic
    n = due.shape[0]
    late = np.zeros(n)
    ready = np.full(n, np.inf)
    resolved = np.full(n, np.inf)
    answers = [None] * n
    errors = {}
    done_q: "queue.Queue" = queue.Queue()
    next_req = iter(range(n))
    next_lock = threading.Lock()
    stats0 = server.stats()
    drain_until = [float("inf")]

    def collect():
        got = 0
        while got < n:
            try:
                i, fut = done_q.get(timeout=0.05)
            except queue.Empty:
                if time.perf_counter() > drain_until[0]:
                    return
                continue
            got += 1
            if fut is None:
                continue
            try:
                y = fut.result()
            except BaseException as e:     # noqa: BLE001 -- a failure
                errors[i] = type(e).__name__
                continue
            with run.span("bench.result_wait"):
                y.block_until_ready()
            ready[i] = time.perf_counter()
            answers[i] = y

    def submitter():
        while True:
            with next_lock:
                i = next(next_req, None)
            if i is None:
                return
            delay = t0 + due[i] - time.perf_counter()
            if delay > 0:
                with run.span("bench.generator_sleep"):
                    time.sleep(delay)
            late[i] = time.perf_counter() - (t0 + due[i])
            try:
                with run.span("bench.dispatch"):
                    fut = server.submit(xs[i])
            except Exception as e:          # noqa: BLE001 -- shed, closed
                errors[i] = type(e).__name__
                done_q.put((i, None))
                continue
            fut.add_done_callback(lambda f, i=i: _resolved(i, f))

    def _resolved(i, fut):
        resolved[i] = time.perf_counter()
        done_q.put((i, fut))

    collector = threading.Thread(target=collect, name="bench-collect")
    users = [threading.Thread(target=submitter, name=f"bench-user-{k}")
             for k in range(int(tr["users"]))]
    with run.span("bench.window"):
        t0 = time.perf_counter()
        collector.start()
        for u in users:
            u.start()
        time.sleep(max(0.0, t0 + run.seconds / 2 - time.perf_counter()))
        backlog_mid = _outstanding(t0 + due, ready)
        for u in users:
            u.join()
        t_sent = time.perf_counter()
        backlog = _outstanding(t0 + due, ready)
        drain_until[0] = t_sent + float(tr["drain_s"])
        collector.join()
    # answers that never came fail the check, not the run
    stats1 = server.stats()
    lat = ready - (t0 + due)
    ok = np.isfinite(lat)
    run.layer["batches"] = {k: stats1[k] - stats0[k]
                            for k in ("requests", "batches")}
    widths, carried = _batch_spans(server, t0, resolved,
                                   run.layer["batches"]["batches"])
    run.layer["products"] = widths
    run.layer["queue_waits_s"] = (None if carried is None
                                  else list(carried - (t0 + due)))
    shape, _, _, values = state["csr"]
    run.layer["nnz"], run.layer["shape"] = int(values.shape[0]), shape
    run.layer["value_bytes"] = np.dtype(values.dtype).itemsize
    state["answers"] = answers
    lateness = openloop.lateness_summary(late)
    return {"attempted": n, "failed": int(n - ok.sum()),
            "metrics": {"p50_ms": openloop.percentile(lat, 50) * 1e3,
                        "p95_ms": openloop.percentile(lat, 95) * 1e3},
            "notes": {"plan": run.layer["plan"], "requests": n,
                      "offered_per_s": n / run.seconds,
                      "achieved_per_s": float(ok.sum()) / max(
                          1e-9, float(np.max(np.where(ok, ready, 0)))
                          - t0),
                      "backlog_mid": backlog_mid,
                      "backlog_at_last_submit": backlog,
                      "sent_s": t_sent - t0,
                      "generator_lateness_ms": lateness,
                      "errors": sorted(set(errors.values())),
                      "mean_batch": (run.layer["batches"]["requests"]
                                     / max(1, run.layer["batches"]
                                           ["batches"]))}}


def _outstanding(due_abs, ready) -> int:
    """Requests already due and not yet answered, now."""
    now = time.perf_counter()
    return int(np.sum(due_abs <= now) - np.sum(ready <= now))


def _batch_spans(server, t0: float, resolved: np.ndarray, nbatches: int):
    """The window's ``serve.batch`` spans as (start, width), and per request
    the start of the span of the batch that carried it: the batch whose
    span ended last before the request's future resolved (the executor
    resolves a batch's futures right after its span closes, before it
    opens the next). ``None`` where the span buffer lost some."""
    epoch = server.registry.epoch
    spans = sorted(((epoch + s.t_start, epoch + s.t_start + s.duration_s,
                     int(s.attrs.get("n", 1)))
                    for s in server.registry.spans()
                    if s.name == "serve.batch" and epoch + s.t_start >= t0))
    if len(spans) != nbatches:
        return None, None
    ends = np.array([e for _, e, _ in spans])
    k = np.searchsorted(ends, resolved, side="right") - 1
    carried = np.where((k >= 0) & np.isfinite(resolved),
                       np.array([s for s, _, _ in spans])[np.maximum(k, 0)],
                       np.nan)
    return [w for _, _, w in spans], carried


def release(state):
    state.pop("server").close(timeout=30.0)
    state.pop("xs")


def check(run, state):
    from bench.reference import CSRReference
    shape, rowptr, colidx, values = state["csr"]
    answers = state["answers"]
    missing = sum(a is None for a in answers)
    gap = float("inf")
    if not missing:
        ref = CSRReference(shape, rowptr, colidx, values)
        Y = jnp.stack(answers, axis=1)
        gap = ref.rel_gap(Y, jnp.asarray(state["xs_host"]).T)
    return {"answers_missing": {"value": float(missing), "limit": 0.0},
            "rel_gap": {"value": gap,
                        "limit": float(run.config["limits"]["rel_gap"])}}
