"""tools/spc5_lint.py: the AST rule engine that guards the architecture.

The real tree must lint clean; synthesized trees planted with violations
must fire exactly the matching rule (mutation coverage for the linter
itself, mirroring tests/test_verify.py's approach for the plan checker).
"""
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "spc5_lint", os.path.join(REPO, "tools", "spc5_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # dataclasses resolve through sys.modules
    spec.loader.exec_module(mod)
    return mod


L = _load_lint()


_SEQ = iter(range(10**6))


def plant(tmp_path, rel, source):
    """Write one file into a FRESH synthetic src/repro tree; returns its
    root (each call isolates, so findings never leak between plants)."""
    root = tmp_path / f"tree{next(_SEQ)}"
    p = root / "src" / "repro" / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return str(root)


# ----------------------------------------------------------------------------
# The real tree is clean
# ----------------------------------------------------------------------------

def test_real_tree_is_clean():
    findings = L.run(REPO)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_clean_and_list_rules():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "spc5_lint.py")],
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "clean" in out.stdout
    listed = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "spc5_lint.py"),
         "--list-rules"], capture_output=True, text=True, env=env)
    assert set(listed.stdout.split()) == set(L.rule_names())


# ----------------------------------------------------------------------------
# Planted violations fire exactly the matching rule
# ----------------------------------------------------------------------------

def test_layout_dispatch_literal_comparison(tmp_path):
    root = plant(tmp_path, "kernels/bad.py", """
        def f(h):
            if h.layout == "panels":
                return 1
            return 0
    """)
    findings = L.check_layout_dispatch(root)
    assert len(findings) == 1
    assert findings[0].rule == "layout-dispatch"
    assert "'panels'" in findings[0].message
    assert findings[0].line == 3


def test_layout_dispatch_handle_construction(tmp_path):
    root = plant(tmp_path, "core/bad.py", """
        from repro.core.ref_spmv import SPC5Device

        def f(arrays, h):
            if isinstance(h, SPC5Device):
                return h
            return SPC5Device(*arrays)
    """)
    rules = {f.message.split(";")[0] for f in L.check_layout_dispatch(root)}
    assert len(rules) == 2              # the isinstance AND the construction


def test_layout_dispatch_allowlist(tmp_path):
    src = 'X = 1 if "panels" == "panels" else 0\n'
    root = plant(tmp_path, "core/plan.py", src)
    assert L.check_layout_dispatch(root) == []
    root2 = plant(tmp_path, "core/other.py", src)
    assert len(L.check_layout_dispatch(root2)) >= 1


def test_pallas_call_outside_kernels(tmp_path):
    root = plant(tmp_path, "core/bad.py", """
        from jax.experimental import pallas as pl

        def f(kernel, x):
            return pl.pallas_call(kernel, out_shape=x)(x)
    """)
    findings = L.check_pallas_call(root)
    assert [f.rule for f in findings] == ["pallas-call"]
    # the same call under kernels/ is the sanctioned launch point
    root2 = plant(tmp_path, "kernels/good.py", """
        from jax.experimental import pallas as pl

        def f(kernel, x):
            return pl.pallas_call(kernel, out_shape=x)(x)
    """)
    assert all("kernels" not in f.path for f in L.check_pallas_call(root2))


def test_dense_materialisation_in_core(tmp_path):
    root = plant(tmp_path, "core/bad.py", """
        import numpy as np

        def f(mat, nrows, ncols):
            d = np.zeros((nrows, ncols))
            return d + mat.todense()
    """)
    findings = L.check_no_dense_in_core(root)
    assert len(findings) == 2
    assert all(f.rule == "no-dense-in-core" for f in findings)
    # formats.py owns the dense<->sparse boundary
    root2 = plant(tmp_path, "core/formats.py", """
        import numpy as np

        def to_dense(mat, nrows, ncols):
            return np.zeros((nrows, ncols))
    """)
    assert L.check_no_dense_in_core(root2) == []
    # 1-D allocations and non-matrix shapes are fine anywhere
    root3 = plant(tmp_path, "core/ok.py", """
        import numpy as np

        def f(nrows, cb):
            return np.zeros(nrows), np.zeros((cb, 8))
    """)
    assert L.check_no_dense_in_core(root3) == []


def test_planted_tree_cli_exits_nonzero(tmp_path):
    root = plant(tmp_path, "core/bad.py", 'X = h == "panels"\n')
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "spc5_lint.py"),
         "--root", root, "--rule", "layout-dispatch"],
        capture_output=True, text=True, env=env)
    assert out.returncode == 1
    assert "[layout-dispatch]" in out.stdout


# ----------------------------------------------------------------------------
# Runtime rules (registry + record schema introspection)
# ----------------------------------------------------------------------------

def test_layout_lowerings_declared_clean():
    assert L.check_layout_vmem_contracts(REPO) == []


def test_layout_lowerings_detects_drift(monkeypatch):
    from repro.kernels import spc5_spmm
    contracts = dict(spc5_spmm.SPMM_VMEM_CONTRACTS)
    del contracts["panels"]
    monkeypatch.setattr(spc5_spmm, "SPMM_VMEM_CONTRACTS", contracts)
    findings = L.check_layout_vmem_contracts(REPO)
    # the panels layout lost its SpMM contract -> the drift is caught
    assert [f.message.split(":")[0] for f in findings] == ["layout 'panels'"]
    assert "no SPMM VMEM contract" in findings[0].message


def test_record_schema_sync_clean():
    assert L.check_record_schema_sync(REPO) == []


def test_record_schema_sync_detects_drift(monkeypatch):
    from repro.core import selector as S

    def add(self, kernel, avg):             # signature out of sync
        raise NotImplementedError

    monkeypatch.setattr(S.RecordStore, "add", add)
    findings = L.check_record_schema_sync(REPO)
    assert any("out of sync" in f.message for f in findings)


def test_rule_registry_complete():
    assert L.rule_names() == ("fault-points-registered", "layout-dispatch",
                              "layout-vmem-contracts", "no-adhoc-timing",
                              "no-dense-in-core",
                              "no-deprecated-entry-points", "pallas-call",
                              "record-schema-sync", "serve-config-knobs",
                              "vmem-contract-itemsize")
    with pytest.raises(SystemExit):
        L.main(["--rule", "not-a-rule"])


# ----------------------------------------------------------------------------
# Serving-tier rules
# ----------------------------------------------------------------------------

def test_deprecated_entry_points_fire(tmp_path):
    root = plant(tmp_path, "models/bad.py", """
        from repro.kernels import ops

        def f(mat):
            return ops.prepare_panels(mat, pr=128)
    """)
    findings = L.check_no_deprecated_entry_points(root)
    assert [f.rule for f in findings] == ["no-deprecated-entry-points"]
    assert "ops.prepare" in findings[0].message
    # the shim's own module may reference the name (it defines it)
    root2 = plant(tmp_path, "kernels/ops.py", """
        def prepare(mat, **kw): ...
        def prepare_panels(mat, **kw):
            return prepare(mat, **kw)
        X = prepare_panels(None)
    """)
    assert L.check_no_deprecated_entry_points(root2) == []


def test_deprecated_entry_points_scan_benchmarks(tmp_path):
    root = plant(tmp_path, "core/ok.py", "X = 1\n")
    bench = os.path.join(root, "benchmarks")
    os.makedirs(bench)
    with open(os.path.join(bench, "bad.py"), "w") as f:
        f.write("from repro.core import distributed as D\n"
                "sh = D.shard_matrix_panels(None, 8)\n")
    findings = L.check_no_deprecated_entry_points(root)
    assert [f.rule for f in findings] == ["no-deprecated-entry-points"]
    assert "shard_matrix" in findings[0].message


def test_no_adhoc_timing_fires_in_launch(tmp_path):
    root = plant(tmp_path, "launch/bad.py", """
        import time

        def f():
            t0 = time.perf_counter()
            t1 = time.time()
            return t1 - t0
    """)
    findings = L.check_no_adhoc_timing(root)
    assert [f.rule for f in findings] == ["no-adhoc-timing"] * 2
    assert "perf_counter()" in findings[0].message
    assert "time.time()" in findings[1].message


def test_no_adhoc_timing_scans_benchmarks_with_allowlist(tmp_path):
    root = plant(tmp_path, "core/ok.py", "X = 1\n")
    bench = os.path.join(root, "benchmarks")
    os.makedirs(bench)
    clock = "import time\nT = time.perf_counter()\n"
    with open(os.path.join(bench, "timing.py"), "w") as f:
        f.write(clock)                      # the one sanctioned clock user
    with open(os.path.join(bench, "bad.py"), "w") as f:
        f.write(clock)
    findings = L.check_no_adhoc_timing(root)
    assert [os.path.basename(f.path) for f in findings] == ["bad.py"]


def test_no_adhoc_timing_sanctioned_clock_is_clean(tmp_path):
    # obs.monotonic IS perf_counter, but under an auditable name -- the
    # rule keys on the call's trailing name, so the alias passes
    root = plant(tmp_path, "launch/good.py", """
        from repro import obs

        def f():
            with obs.span("work") as sp:
                pass
            return obs.monotonic(), sp.duration_s
    """)
    assert L.check_no_adhoc_timing(root) == []


def test_fault_points_registered_fires(tmp_path):
    root = plant(tmp_path, "launch/bad.py", """
        from repro import obs

        def f(name):
            obs.faults.get_faults().maybe_fail("serve.bogus")
            obs.faults.get_faults().maybe_fail(name)
            if obs.faults.get_faults().check("exec.spmv"):
                raise RuntimeError
    """)
    findings = L.check_fault_points_registered(root)
    bad = [f for f in findings if f.path.endswith("bad.py")]
    assert len(bad) == 2
    msgs = "\n".join(f.message for f in bad)
    assert "'serve.bogus'" in msgs          # uncatalogued literal
    assert "string literal" in msgs         # computed name
    # exec.spmv IS wired in the planted tree; the other catalogued points
    # have no call site there, which the coverage half of the rule reports
    uncovered = [f for f in findings if "no call site" in f.message]
    assert not any("'exec.spmv'" in f.message for f in uncovered)
    assert any("'plan.build'" in f.message for f in uncovered)


def test_fault_points_registered_ignores_unrelated_check(tmp_path):
    # .check() on a non-fault receiver is not an injection site
    root = plant(tmp_path, "core/ok.py", """
        def f(report):
            return report.check("anything-at-all")
    """)
    assert [f for f in L.check_fault_points_registered(root)
            if f.path.endswith("ok.py")] == []


def test_serve_config_knobs_clean_and_fires(tmp_path):
    assert L.check_serve_config_knobs(REPO) == []
    # a literal flag with no ServeConfig field fires; one that maps is fine
    root = plant(tmp_path, "launch/serve.py", """
        import argparse
        ap = argparse.ArgumentParser()
        ap.add_argument("--rogue-knob", type=int, default=0)
        ap.add_argument("--kv-dtype", default="bfloat16")
    """)
    findings = L.check_serve_config_knobs(root)
    assert [f.rule for f in findings] == ["serve-config-knobs"]
    assert "rogue_knob" in findings[0].message
