#!/usr/bin/env python
"""Repo lint: enforce the SPC5 architecture rules statically.

Generalises tests/test_plan.py's substring dispatch scan into an AST-based
rule engine. Each rule is a function ``rule(root) -> list[Finding]``; the
CLI runs all of them (or ``--rule NAME``) over ``--root`` (default: the
repo this file lives in) and exits nonzero on any finding, printing
``path:line: [rule] message`` lines a CI log renders as annotations.

Rules
-----
layout-dispatch
    Layout branching lives in ``repro.core.plan`` only. Nothing else in
    ``src/repro`` compares against layout name literals, constructs the
    legacy device handle tuples, or isinstance-checks handle classes --
    adding a layout is one registration, not five edited files.
pallas-call
    ``pl.pallas_call`` appears only under ``src/repro/kernels/``: the
    kernel boundary is the only place device code is launched.
no-dense-in-core
    ``repro/core`` never materialises a dense (nrows, ncols) matrix:
    no ``.todense()``/``.toarray()`` calls, no full-shape
    ``zeros``/``ones``/``empty``/``full`` allocations outside the format
    converters in ``formats.py`` (which own the dense<->sparse boundary).
layout-vmem-contracts
    Runtime rule: every registered layout with a device view (a Pallas
    path) has both an SpMV and an SpMM VMEM contract, so the verifier can
    bound its kernels' footprint.
record-schema-sync
    Runtime rule: the benchmark record schema is defined once. The
    ``RecordStore.add`` signature mirrors the ``Record`` dataclass fields
    in order, and the JSONL v4 field list matches (16 fields ending in
    ``vdtype``).
vmem-contract-itemsize
    Every VMEM contract helper (``_vmem_*``) in the kernel modules computes
    its footprint from the plan's value ``itemsize`` argument -- a contract
    that hard-codes 4-byte values under-budgets f64 plans and over-budgets
    the bf16/int8 stores.
serve-config-knobs
    Serve knobs are declared once, on ``launch.server.ServeConfig``. Any
    literal ``add_argument("--flag")`` in the launch modules must map back
    to a ServeConfig field (the CLI is supposed to be GENERATED from the
    dataclass via ``add_config_args``; a hand-added flag that bypasses the
    config is the drift this rule catches).
no-deprecated-entry-points
    The deprecated prepare/shard entry points (``prepare_panels``,
    ``prepare_test``, ``shard_matrix_panels``) survive only as
    ``DeprecationWarning`` shims: nothing under ``src/repro`` or
    ``benchmarks`` may call them except the modules that define them
    (tests may, to pin the shims' behaviour).
no-adhoc-timing
    All timing in ``src/repro/launch/`` and ``benchmarks/`` routes through
    ``repro.obs`` (spans / ``obs.monotonic``) or ``benchmarks.timing``: no
    raw ``time.perf_counter()`` / ``time.time()`` calls. Allowlisted:
    ``benchmarks/timing.py`` (the one sanctioned clock user; ``repro.obs``
    itself lives outside the scanned trees). Ad-hoc clocks are how serve
    counters and bench numbers drift out of the exported metrics.
fault-points-registered
    Runtime rule: fault injection is a closed catalogue. Every
    ``maybe_fail(...)`` / fault-registry ``check(...)`` call site in
    ``src/repro`` and ``benchmarks`` names its point as a STRING LITERAL
    found in ``repro.obs.faults.CATALOGUE`` (a computed or uncatalogued
    name silently escapes the CI chaos matrix), and every catalogued
    point is wired at least once (a catalogue entry with no call site is
    a fault the chaos suite believes it covers but never fires).

The rules are importable (tests/test_lint.py, and test_plan.py's dispatch
test is a thin wrapper over ``layout-dispatch``); the CLI is what CI runs.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import os
import sys
from typing import Callable, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Layout name string literals whose comparison constitutes dispatch.
LAYOUT_LITERALS = {"panels", "whole_vector", "whole", "test"}

#: Legacy handle constructors / classes nothing outside plan.py may touch.
HANDLE_NAMES = {"SPC5Device", "SPC5PanelDevice"}

#: Files allowed to branch on layout: the registry itself, the reference
#: interpreter that defines the device views, and the selector's record
#: schema (records *name* layouts; that is data, not dispatch).
DISPATCH_ALLOWLIST = {
    os.path.join("core", "plan.py"),
    os.path.join("core", "ref_spmv.py"),
    os.path.join("core", "selector.py"),
}

#: core/ files allowed to touch dense matrices: the converters.
DENSE_ALLOWLIST = {
    os.path.join("core", "formats.py"),
    os.path.join("core", "matgen.py"),
    os.path.join("core", "ref_spmv.py"),
}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-root-relative
    line: int
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


_RULES: Dict[str, Callable[[str], List[Finding]]] = {}


def _rule(name: str):
    def deco(fn):
        _RULES[name] = fn
        fn.rule_name = name
        return fn
    return deco


def rule_names():
    return tuple(sorted(_RULES))


# ----------------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------------

def _py_files(root: str, sub: str):
    """Yield (abspath, relpath-to-``sub``) for .py files under root/sub."""
    base = os.path.join(root, sub)
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                ap = os.path.join(dirpath, fn)
                yield ap, os.path.relpath(ap, base)


def _parse(path: str) -> Optional[ast.AST]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        return ast.parse(src, filename=path)
    except SyntaxError:
        return None    # broken files are the tier-1 suite's problem


def _rel(root: str, path: str) -> str:
    return os.path.relpath(path, root)


def _call_name(node: ast.Call) -> str:
    """Trailing name of the called expression: f(), m.f() -> 'f'."""
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return ""


# ----------------------------------------------------------------------------
# static rules
# ----------------------------------------------------------------------------

@_rule("layout-dispatch")
def check_layout_dispatch(root: str = REPO_ROOT) -> List[Finding]:
    out: List[Finding] = []
    for ap, rel in _py_files(root, os.path.join("src", "repro")):
        if rel in DISPATCH_ALLOWLIST:
            continue
        tree = _parse(ap)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Compare):
                consts = [n for n in [node.left] + list(node.comparators)
                          if isinstance(n, ast.Constant)
                          and n.value in LAYOUT_LITERALS]
                if consts:
                    out.append(Finding(
                        "layout-dispatch", _rel(root, ap), node.lineno,
                        f"comparison against layout literal "
                        f"{consts[0].value!r}; dispatch belongs in "
                        f"repro.core.plan"))
            elif isinstance(node, ast.Call):
                name = _call_name(node)
                if name in HANDLE_NAMES:
                    out.append(Finding(
                        "layout-dispatch", _rel(root, ap), node.lineno,
                        f"direct {name}(...) construction; only the layout "
                        f"registry builds device views"))
                elif (name == "isinstance" and len(node.args) == 2):
                    names = {n.id for n in ast.walk(node.args[1])
                             if isinstance(n, ast.Name)}
                    hit = names & HANDLE_NAMES
                    if hit:
                        out.append(Finding(
                            "layout-dispatch", _rel(root, ap), node.lineno,
                            f"isinstance check against {sorted(hit)[0]}; "
                            f"branch on plan.layout inside repro.core.plan "
                            f"instead"))
    return out


@_rule("pallas-call")
def check_pallas_call(root: str = REPO_ROOT) -> List[Finding]:
    out: List[Finding] = []
    kernels_prefix = "kernels" + os.sep
    for ap, rel in _py_files(root, os.path.join("src", "repro")):
        if rel.startswith(kernels_prefix):
            continue
        tree = _parse(ap)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    _call_name(node) == "pallas_call":
                out.append(Finding(
                    "pallas-call", _rel(root, ap), node.lineno,
                    "pl.pallas_call outside repro/kernels/; device code "
                    "launches only at the kernel boundary"))
    return out


@_rule("no-dense-in-core")
def check_no_dense_in_core(root: str = REPO_ROOT) -> List[Finding]:
    out: List[Finding] = []
    alloc_names = {"zeros", "ones", "empty", "full"}
    dim_names = {"nrows", "ncols"}
    for ap, rel in _py_files(root, os.path.join("src", "repro", "core")):
        if os.path.join("core", rel) in DENSE_ALLOWLIST:
            continue
        tree = _parse(ap)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name in ("todense", "toarray"):
                out.append(Finding(
                    "no-dense-in-core", _rel(root, ap), node.lineno,
                    f".{name}() in repro/core/; dense materialisation is "
                    f"confined to the formats.py converters"))
            elif name in alloc_names and node.args:
                shape = node.args[0]
                if isinstance(shape, ast.Tuple) and len(shape.elts) == 2:
                    idents = {n.id for n in ast.walk(shape)
                              if isinstance(n, ast.Name)}
                    idents |= {n.attr for n in ast.walk(shape)
                               if isinstance(n, ast.Attribute)}
                    if idents & dim_names:
                        out.append(Finding(
                            "no-dense-in-core", _rel(root, ap), node.lineno,
                            f"{name}((...nrows/ncols...)) allocates a "
                            f"dense-matrix-sized buffer in repro/core/"))
    return out


#: Deprecated entry points and the module that is allowed to define/call
#: each (the shim's own home).
DEPRECATED_ENTRY_POINTS = {
    "prepare_panels": os.path.join("kernels", "ops.py"),
    "prepare_test": os.path.join("kernels", "ops.py"),
    "shard_matrix_panels": os.path.join("core", "distributed.py"),
}


@_rule("no-deprecated-entry-points")
def check_no_deprecated_entry_points(root: str = REPO_ROOT) -> List[Finding]:
    out: List[Finding] = []
    scans = [(os.path.join("src", "repro"), True), ("benchmarks", False)]
    for sub, is_src in scans:
        if not os.path.isdir(os.path.join(root, sub)):
            continue
        for ap, rel in _py_files(root, sub):
            tree = _parse(ap)
            if tree is None:
                continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node)
                home = DEPRECATED_ENTRY_POINTS.get(name)
                if home is None or (is_src and rel == home):
                    continue
                out.append(Finding(
                    "no-deprecated-entry-points", _rel(root, ap),
                    node.lineno,
                    f"{name}(...) is a deprecation shim; call the unified "
                    f"entry point ({'ops.prepare' if 'prepare' in name else 'distributed.shard_matrix'}) with keywords instead"))
    return out


#: (scan subtree, allowlisted rel-paths) for the ad-hoc-timing ban.
TIMING_SCANS = (
    (os.path.join("src", "repro", "launch"), frozenset()),
    ("benchmarks", frozenset({"timing.py"})),
)


@_rule("no-adhoc-timing")
def check_no_adhoc_timing(root: str = REPO_ROOT) -> List[Finding]:
    out: List[Finding] = []
    for sub, allow in TIMING_SCANS:
        if not os.path.isdir(os.path.join(root, sub)):
            continue
        for ap, rel in _py_files(root, sub):
            if rel in allow:
                continue
            tree = _parse(ap)
            if tree is None:
                continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node)
                bad = None
                if name == "perf_counter":
                    bad = "perf_counter()"
                elif (name == "time"
                      and isinstance(node.func, ast.Attribute)
                      and isinstance(node.func.value, ast.Name)
                      and node.func.value.id == "time"):
                    bad = "time.time()"
                if bad:
                    out.append(Finding(
                        "no-adhoc-timing", _rel(root, ap), node.lineno,
                        f"raw {bad}; route timing through repro.obs "
                        f"(span / obs.monotonic) or benchmarks.timing"))
    return out


# ----------------------------------------------------------------------------
# runtime rules (import the tree they lint)
# ----------------------------------------------------------------------------

def _import_repro(root: str):
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


@_rule("layout-vmem-contracts")
def check_layout_vmem_contracts(root: str = REPO_ROOT) -> List[Finding]:
    _import_repro(root)
    from repro.core import plan as P
    from repro.kernels.spc5_spmm import SPMM_VMEM_CONTRACTS
    from repro.kernels.spc5_spmv import SPMV_VMEM_CONTRACTS
    out: List[Finding] = []
    rel = os.path.join("src", "repro", "core", "plan.py")
    for name in P.layout_names():
        if P.get_layout(name).device_view is None:
            continue    # no pallas path registered; contracts don't apply
        for label, contracts in (("SPMV", SPMV_VMEM_CONTRACTS),
                                 ("SPMM", SPMM_VMEM_CONTRACTS)):
            if name not in contracts:
                out.append(Finding(
                    "layout-vmem-contracts", rel, 1,
                    f"layout {name!r}: no {label} VMEM contract (kernels "
                    f"declare their footprint so the verifier can bound "
                    f"it)"))
    return out


@_rule("record-schema-sync")
def check_record_schema_sync(root: str = REPO_ROOT) -> List[Finding]:
    _import_repro(root)
    import inspect

    from repro.core import selector as S
    out: List[Finding] = []
    rel = os.path.join("src", "repro", "core", "selector.py")
    fields = [f.name for f in dataclasses.fields(S.Record)]
    add_params = [p for p in
                  inspect.signature(S.RecordStore.add).parameters
                  if p != "self"]
    if add_params != fields:
        out.append(Finding(
            "record-schema-sync", rel, 1,
            f"RecordStore.add params {add_params} out of sync with Record "
            f"fields {fields}"))
    if fields[-1] != "vdtype" or len(fields) != 16:
        out.append(Finding(
            "record-schema-sync", rel, 1,
            f"Record schema drifted from JSONL v4 (16 fields ending in "
            f"'vdtype'); got {len(fields)} fields ending in "
            f"{fields[-1]!r} -- bump RECORDS_VERSION"))
    return out


@_rule("vmem-contract-itemsize")
def check_vmem_contract_itemsize(root: str = REPO_ROOT) -> List[Finding]:
    out: List[Finding] = []
    for fn in ("spc5_spmv.py", "spc5_spmm.py"):
        rel = os.path.join("src", "repro", "kernels", fn)
        ap = os.path.join(root, rel)
        if not os.path.exists(ap):
            continue
        tree = _parse(ap)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.FunctionDef)
                    and node.name.startswith("_vmem_")):
                continue
            used = {n.id for n in ast.walk(node)
                    if isinstance(n, ast.Name)}
            if "itemsize" not in used:
                out.append(Finding(
                    "vmem-contract-itemsize", rel, node.lineno,
                    f"VMEM contract {node.name} never reads 'itemsize'; "
                    f"compute the footprint from the plan's value itemsize "
                    f"(a hard-coded 4 misbudgets f64/bf16/int8 stores)"))
    return out


@_rule("serve-config-knobs")
def check_serve_config_knobs(root: str = REPO_ROOT) -> List[Finding]:
    _import_repro(root)
    from repro.launch.server import ServeConfig
    fields = {f.name for f in dataclasses.fields(ServeConfig)}
    out: List[Finding] = []
    launch = os.path.join("src", "repro", "launch")
    for fn in ("serve.py", "server.py"):
        ap_path = os.path.join(root, launch, fn)
        if not os.path.exists(ap_path):
            continue
        tree = _parse(ap_path)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _call_name(node) == "add_argument" and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            flag = node.args[0].value
            knob = flag.lstrip("-").replace("-", "_")
            if knob not in fields:
                out.append(Finding(
                    "serve-config-knobs", os.path.join(launch, fn),
                    node.lineno,
                    f"literal CLI knob {flag!r} has no ServeConfig field "
                    f"{knob!r}; declare serve knobs on the dataclass and "
                    f"let add_config_args generate the flag"))
    return out


#: The fault registry itself resolves point names from variables (its own
#: plumbing, not a wired injection site).
FAULTS_ALLOWLIST = {
    os.path.join("src", "repro", "obs", "faults.py"),
}


@_rule("fault-points-registered")
def check_fault_points_registered(root: str = REPO_ROOT) -> List[Finding]:
    _import_repro(root)
    from repro.obs.faults import CATALOGUE
    out: List[Finding] = []
    wired: Dict[str, int] = {}

    def _is_fault_call(node: ast.Call) -> bool:
        name = _call_name(node)
        if name == "maybe_fail":
            return True
        if name != "check" or not isinstance(node.func, ast.Attribute):
            return False
        # .check() is everywhere; only a fault-registry receiver counts
        # (faults.check, get_faults().check, self._faults_now().check)
        return "fault" in ast.unparse(node.func.value).lower()

    for sub in (os.path.join("src", "repro"), "benchmarks"):
        if not os.path.isdir(os.path.join(root, sub)):
            continue
        for ap, _ in _py_files(root, sub):
            rel = _rel(root, ap)
            if rel in FAULTS_ALLOWLIST:
                continue
            tree = _parse(ap)
            if tree is None:
                continue
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and _is_fault_call(node)):
                    continue
                if not (node.args and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    out.append(Finding(
                        "fault-points-registered", rel, node.lineno,
                        "fault point must be a string literal; a computed "
                        "name escapes the catalogue and the CI chaos "
                        "matrix"))
                    continue
                point = node.args[0].value
                if point not in CATALOGUE:
                    out.append(Finding(
                        "fault-points-registered", rel, node.lineno,
                        f"fault point {point!r} is not in "
                        f"repro.obs.faults.CATALOGUE; register it there "
                        f"(name, where-it-fires) so the chaos matrix "
                        f"covers it"))
                    continue
                wired[point] = wired.get(point, 0) + 1
    for point in sorted(set(CATALOGUE) - set(wired)):
        out.append(Finding(
            "fault-points-registered",
            os.path.join("src", "repro", "obs", "faults.py"), 1,
            f"catalogued fault point {point!r} has no call site under "
            f"src/repro or benchmarks; the chaos matrix believes it is "
            f"covered but it never fires"))
    return out


# ----------------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------------

def run(root: str = REPO_ROOT, rules=None) -> List[Finding]:
    findings: List[Finding] = []
    for name in (rules or rule_names()):
        findings.extend(_RULES[name](root))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=REPO_ROOT,
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--rule", action="append", choices=rule_names(),
                    help="run only this rule (repeatable)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)
    if args.list_rules:
        for name in rule_names():
            print(name)
        return 0
    findings = run(os.path.abspath(args.root), args.rule)
    for f in findings:
        print(f)
    if findings:
        print(f"spc5_lint: {len(findings)} finding(s)")
        return 1
    print(f"spc5_lint: clean ({len(args.rule or rule_names())} rule(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
