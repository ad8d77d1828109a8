"""How one run of one cell goes, whatever the cell.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything that belongs to one of them is found by name, so a later cell
adds files and entries and edits none:

- ``bench/configs/<config>.json``: the deployment as it is run; its
  ``generator`` names ``bench/gen/<generator>.py``, which makes the
  matrix from the config and the seed;
- ``bench/traffic/<traffic>.json``: the mix's parameters; its ``loop``
  names ``bench/loops/<loop>.py``, the loop that drives the window;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A loop module defines ``setup(run)``, ``window(run, state)``,
``release(state)`` and ``check(run, state)``; :func:`run_cell` calls them
in that order, so set-up ends before the window opens, nothing of the
program is alive when the reference runs, and the reference never counts
in the window or in ``setup_s``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "bench")
#: JAX's persistent compilation cache: one fixed path inside the checkout,
#: so that every run after a checkout's first finds its programs there.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: The host span that brackets the measured window in a traced run.
WINDOW_SPAN = "bench.window"


class BenchError(RuntimeError):
    """A run that cannot give a result: no chip, a missing file."""


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file {os.path.relpath(path, ROOT)}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, benchmark: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its config, its traffic
    and the metrics it reports."""
    bench = benchmark or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, cfg["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def import_program() -> None:
    """Put the library under test (``<checkout>/src``) on the path."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_chips(chips: int):
    """The devices of a run: the first ``chips`` TPU devices, or
    :class:`BenchError` where JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} "
                         f"({devs[0].device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """Every program of the run goes to, and comes from, ``CACHE_DIR``."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


class CompileCounter:
    """Counts the programs JAX builds (compiled or loaded from the cache)
    while armed: the measured window should build none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        self.armed = False
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._on_event)


class Run:
    """One run of one cell: its inputs, and what the loop leaves for the
    per-layer readers in ``layer``."""

    def __init__(self, cell: Cell, seed: int, seconds: float, devices,
                 rehearsal: bool = False,
                 wrap_product: Optional[Callable] = None):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.devices = devices
        self.config = dict(cell.config)
        self.traffic = dict(cell.traffic)
        if rehearsal:
            self.config.update(self.config.pop("rehearsal", {}))
            self.traffic.update(self.traffic.pop("rehearsal", {}))
        self.layer: Dict[str, object] = {}
        self.reduction = None
        self.peaks = None
        self._wrap_product = wrap_product
        self._undo: List[Callable] = []

    @contextlib.contextmanager
    def span(self, name: str, key: Optional[str] = None):
        """A host span of the benchmark's own: in a traced run it shows in
        the trace; with ``key`` its seconds land in ``layer[key]``."""
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        if key is not None:
            self.layer[key] = time.perf_counter() - t

    def generate(self):
        """The cell's matrix as CSR arrays ``(shape, rowptr, colidx,
        values)``, from the config's generator and the seed. Where a
        product wrapper was given (a control or a planted fault), it is
        put in the program's place here."""
        gen = load_plugin("gen", self.config["generator"])
        csr = gen.generate(self.config, self.seed)
        if self._wrap_product is not None:
            self._install_wrapper(csr)
        return csr

    def _install_wrapper(self, csr) -> None:
        from repro.core import plan as P
        spmv, spmm = P.execute_spmv, P.execute_spmm
        P.execute_spmv, P.execute_spmm = self._wrap_product(spmv, spmm, csr)
        self._undo.append(lambda: (setattr(P, "execute_spmv", spmv),
                                   setattr(P, "execute_spmm", spmm)))

    def close(self) -> None:
        while self._undo:
            self._undo.pop()()


@contextlib.contextmanager
def rehearsal_program():
    """The CPU rehearsal of the harness's tests: the library takes its TPU
    decisions (panels layout, mask lowering, Pallas kernels) and runs its
    kernels in interpret mode. Never used by a measured run."""
    from repro.core import plan as P
    saved = P._on_tpu, P.execute_spmv, P.execute_spmm

    def interpreted(fn):
        def call(*args, **kw):
            if kw.get("interpret") is None:
                kw["interpret"] = True
            return fn(*args, **kw)
        return call

    P._on_tpu = lambda: True
    P.execute_spmv = interpreted(saved[1])
    P.execute_spmm = interpreted(saved[2])
    try:
        yield
    finally:
        P._on_tpu, P.execute_spmv, P.execute_spmm = saved


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@contextlib.contextmanager
def profiled(run: Run, keep_dir: Optional[str]):
    """Trace the device and the host while the body runs; on exit the
    reduction of that trace is in ``run.reduction``."""
    import jax
    from bench import trace_reduce
    out = keep_dir or tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(out)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    try:
        paths = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise BenchError(f"the profiler wrote no trace under {out}")
        run.reduction = trace_reduce.reduce(max(paths, key=os.path.getmtime),
                                            window=WINDOW_SPAN)
    finally:
        if keep_dir is None:
            shutil.rmtree(out, ignore_errors=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             devices, rehearsal: bool = False,
             wrap_product: Optional[Callable] = None,
             trace_dir: Optional[str] = None) -> dict:
    """One run of ``cell``: set-up, the window, the check. Returns the
    result object the last line of ``run.py`` prints."""
    import jax
    from bench import peaks as PK
    loop = load_plugin("loops", cell.traffic["loop"])
    run = Run(cell, seed, seconds, devices, rehearsal=rehearsal,
              wrap_product=wrap_product)
    counter = CompileCounter()
    stack = contextlib.ExitStack()
    try:
        stack.callback(counter.close)
        if rehearsal:
            stack.enter_context(rehearsal_program())
        stack.callback(run.close)     # before the rehearsal is undone
        state = loop.setup(run)
        setup_s = time.perf_counter() - t0
        try:
            counter.armed = True
            with (profiled(run, trace_dir) if trace and not rehearsal
                  else contextlib.nullcontext()):
                out = loop.window(run, state)
            counter.armed = False
            peak = memory_peak_bytes(devices)
        finally:
            loop.release(state)
        checks = loop.check(run, state)
    finally:
        stack.close()
    correct = all(c["value"] is not None and not math.isnan(c["value"])
                  and c["value"] <= c["limit"] for c in checks.values())
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics: Dict[str, dict] = {}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if rehearsal:
        # a CPU rehearsal is never reported under a device metric's name
        result["rehearsal"] = {"setup_s_cpu": setup_s,
                               "window": out["metrics"]}
    elif trace:
        run.peaks = PK.chip_peaks(d0.device_kind)
        red = run.reduction
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        for m in cell.per_layer:
            value = load_plugin("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    result["window_programs_built"] = counter.count
    result["notes"] = out.get("notes", {})
    result["checks"] = checks
    return result
