import pytest

from bench import trace_reduce as T

MS = 1_000_000  # ns


def _op(name, module, a, b, kernel=False):
    return T.Op(name, module, a * MS, b * MS, kernel)


def synthetic():
    ops = [
        _op("custom-call.1", "jit_spmv_pallas_panels(1)", 10, 60, True),
        _op("fusion.2", "jit_spmv_pallas_panels(1)", 60, 62),
        _op("fusion.3", "jit_bench_cg_update(2)", 62, 63),
        _op("custom-call.1", "jit_spmv_pallas_panels(1)", 70, 90, True),
        _op("copy.4", "jit_spmv_pallas_panels(1)", 90, 95),
        _op("fusion.9", "jit_spmv_pallas_panels(1)", 120, 130),  # after
    ]
    host = [("bench.window", 0 * MS, 100 * MS),
            ("bench.dispatch", 0 * MS, 9 * MS),
            ("bench.result_wait", 63 * MS, 71 * MS),
            ("bench.cg_vector_ops", 95 * MS, 100 * MS)]
    return T.Trace(ops={"/device:TPU:0": ops}, host_spans=host)


def test_reduction_of_a_synthetic_trace():
    r = T.reduce_trace(synthetic())
    assert r.window_s == pytest.approx(0.1)
    # busy: 10-63 and 70-95 inside the window
    assert r.busy_s == pytest.approx(0.053 + 0.025)
    assert r.kernel_s == pytest.approx(0.070) and r.kernel_calls == 2
    assert r.bench_s == pytest.approx(0.001)
    assert r.library_s == pytest.approx(0.002 + 0.005)
    assert r.device_ops[0] == ["jit_spmv_pallas_panels(1)/custom-call.1",
                               pytest.approx(0.070)]
    gaps = {round(s, 6): label for label, s in r.idle_gaps}
    assert gaps == {0.01: "bench.dispatch", 0.007: "bench.result_wait",
                    0.005: "bench.cg_vector_ops"}


def test_ops_cut_by_the_window_count_only_their_inside_part():
    t = synthetic()
    t.host_spans[0] = ("bench.window", 20 * MS, 80 * MS)
    r = T.reduce_trace(t)
    assert r.window_s == pytest.approx(0.06)
    assert r.kernel_s == pytest.approx(0.040 + 0.010)


def test_busy_is_averaged_over_chips_and_missing_window_is_an_error():
    t = synthetic()
    t.ops["/device:TPU:1"] = [_op("custom-call.1", "m", 0, 100, True)]
    r = T.reduce_trace(t)
    assert r.busy_s == pytest.approx((0.078 + 0.100) / 2)
    with pytest.raises(ValueError):
        T.reduce_trace(T.Trace(ops={}, host_spans=[]))


def test_mosaic_is_matched_by_its_custom_call_target_not_its_name():
    assert T.is_mosaic("anything", {"long_name": "custom-call(...), "
                                    "custom_call_target=\"tpu_custom_call\""})
    assert not T.is_mosaic("custom-call.1", {"long_name": "fusion(...)"})


def test_a_recorded_trace_is_read_with_jax_own_reader():
    """``data/cpu_window.xplane.pb`` was recorded by ``jax.profiler`` on the
    CPU around two dispatch / wait pairs: it holds the host spans and no
    TPU plane, so the window is read and nothing is busy."""
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "cpu_window.xplane.pb")
    t = T.load(path)
    assert t.ops == {}
    names = [n for n, _, _ in t.host_spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 2
    assert names.count("bench.result_wait") == 2
    win = [(s, e) for n, s, e in t.host_spans if n == "bench.window"][0]
    for n, s, e in t.host_spans:
        assert win[0] <= s <= e <= win[1]
    r = T.reduce_trace(t)
    assert r.chips == 0 and r.busy_s == 0 and r.window_s > 0


def test_readers_find_nothing_to_read_without_a_chip_plane():
    import types

    from bench.harness import BenchError, load_plugin
    red = T.reduce_trace(T.Trace(ops={}, host_spans=[
        ("bench.window", 0, 10 * MS)]))
    run = types.SimpleNamespace(reduction=red, layer={"products": [1]},
                                peaks=None)
    for name in ("idle_pct", "wrapper_ms"):
        assert load_plugin("metrics", name).read(run) is None
    # products ran, yet no kernel event: the reduction lost the kernel
    with pytest.raises(BenchError):
        load_plugin("metrics", "panel_kernel_roofline").read(run)
    run.layer["products"] = []
    assert load_plugin("metrics", "panel_kernel_roofline").read(run) is None


def test_a_kernel_cell_whose_trace_has_no_mosaic_event_fails():
    import types

    from bench.harness import BenchError, load_plugin
    t = synthetic()
    t.ops["/device:TPU:0"] = [T.Op(o.name, o.module, o.start_ns, o.end_ns,
                                   False) for o in t.ops["/device:TPU:0"]]
    run = types.SimpleNamespace(reduction=T.reduce_trace(t),
                                layer={"products": [1, 1]}, peaks=None)
    with pytest.raises(BenchError):
        load_plugin("metrics", "panel_kernel_roofline").read(run)


def test_a_recorded_v5e_trace_finds_the_kernel_and_the_benchmark_programs():
    """``data/v5e_hpcg_window.xplane.pb`` was recorded on a TPU v5e by a
    ``--trace 1`` run of ``hpcg104.cg`` with a 10-second window: seven CG
    iterations, each one panel-kernel SpMV (a Mosaic custom call of about
    1.9 s) between the benchmark's own ``jit_bench_*`` programs."""
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_hpcg_window.xplane.pb")
    t = T.load(path)
    assert list(t.ops) == ["/device:TPU:0"]
    ops = t.ops["/device:TPU:0"]
    kernels = [o for o in ops if o.kernel]
    assert len(kernels) == 7
    assert {o.name for o in kernels} == {"%spmv_pallas_panels.1"}
    assert {o.module.split("(")[0] for o in kernels} == {
        "jit_spmv_pallas_panels"}
    assert any(o.module.startswith(T.BENCH_PROGRAM) for o in ops)
    r = T.reduce_trace(t)
    assert r.chips == 1 and r.kernel_calls == 7
    assert 1.8 < r.kernel_s / 7 < 2.0
    assert 0.99 < r.busy_s / r.window_s <= 1.0
    assert 0 < r.bench_s < 0.01 and 0 < r.library_s < 0.05
    assert r.device_ops[0][0] == r.device_ops[0][0].split(" = ")[0]
