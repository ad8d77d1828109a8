"""Auto-tuning tests: record store round-trip, tune(), ops.prepare wiring.

Covers the selector-driven (layout, pr, xw, cb) configuration path:
write -> merge -> fit -> tune round-trips, the empty-store fallback to the
fixed defaults, dimension clamping for stores fitted on large matrices, and
the determinism of the benchmark sweep's record identities (which is what
makes the CI `--quick` artifact comparable across runs; the suite runs
under the deterministic hypothesis fallback shim either way).
"""
import dataclasses
import os
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import formats as F
from repro.core import matgen, selector as S
from repro.core import distributed as D
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_ambient_store(monkeypatch):
    """Keep the env-configured default store out of these tests."""
    monkeypatch.delenv(S.RECORDS_ENV, raising=False)
    S.set_default_store(None)
    yield
    S.set_default_store(None)


def planted_store(best: S.PanelConfig, worse: S.PanelConfig,
                  kernel: str = "2x8") -> S.RecordStore:
    """Store where ``best`` measures strictly faster than ``worse``."""
    st = S.RecordStore()
    r, c = S.kernel_block(kernel)
    for avg in (1.0, 3.0, 6.0):
        f = S.MatrixFeatures(0, 0, 0, 5.0, 2.0, avg, avg / (r * c))
        st.add_measurement(kernel, f, best, 1, 2.0 + avg)
        st.add_measurement(kernel, f, worse, 1, 1.0)
    return st


BEST = S.PanelConfig(layout="panels", pr=16, xw=32, cb=8)
WORSE = S.PanelConfig(layout="whole_vector", pr=0, xw=0, cb=256)


def test_jsonl_roundtrip_full_schema(tmp_path):
    st = planted_store(BEST, WORSE)
    p = str(tmp_path / "records.jsonl")
    st.save_jsonl(p)
    # versioned header on the first line
    import json
    with open(p) as f:
        assert json.loads(f.readline())["spc5_records_version"] \
            == S.RECORDS_VERSION
    st2 = S.RecordStore(p)          # RecordStore() loads JSONL transparently
    assert st2.records == st.records
    # legacy single-JSON-array stores still load, with defaulted new fields
    legacy = S.RecordStore()
    legacy.add("4x8", 12.0, 1, 3.5, matrix="m1", pr=512)
    lp = str(tmp_path / "legacy.json")
    legacy.save(lp)
    st3 = S.RecordStore(lp)
    assert st3.records[0].layout == "" and st3.records[0].xw == 0
    assert st3.records[0].config() == S.PanelConfig("panels", 512, 0, None)
    # legacy layout spellings normalise to the plan registry's key set
    legacy2 = S.RecordStore()
    legacy2.add("1x8", 3.0, 1, 2.0, cb=512, layout="whole")
    l2 = str(tmp_path / "legacy2.json")
    legacy2.save(l2)
    assert S.RecordStore(l2).records[0].layout == "whole_vector"
    assert S.PanelConfig("whole").layout == "whole_vector"


def test_load_records_merges_and_dedups(tmp_path):
    a = planted_store(BEST, WORSE)
    b = S.RecordStore()
    b.add("4x4", 2.0, 8, 9.9, pr=512, xw=1024, cb=64, layout="panels")
    a.save_jsonl(str(tmp_path / "a.jsonl"))
    b.save_jsonl(str(tmp_path / "b.jsonl"))
    b.save_jsonl(str(tmp_path / "b_copy.jsonl"))   # duplicated artifact
    merged = S.load_records(str(tmp_path))
    assert len(merged.records) == len(a.records) + len(b.records)
    assert set(merged.kernels()) == {"2x8", "4x4"}


def test_write_merge_fit_tune_roundtrip(tmp_path):
    """The full pipeline: sweep records -> JSONL files -> merge -> fit ->
    tune returns the config that measured fastest."""
    a = planted_store(BEST, WORSE)
    a.save_jsonl(str(tmp_path / "run1.jsonl"))
    planted_store(BEST, WORSE).save_jsonl(str(tmp_path / "run2.jsonl"))
    store = S.load_records(str(tmp_path))
    pred = S.ConfigPredictor(store, kernel="2x8")
    assert set(pred.configs()) == {BEST, WORSE}
    feats = S.MatrixFeatures(0, 0, 0, 5.0, 2.0, 4.0, 0.25)
    assert pred.predict(feats, BEST) > pred.predict(feats, WORSE)
    assert S.tune(feats, store=store, kernel="2x8") == BEST
    # unknown kernel falls back to kernel-agnostic records, not defaults
    assert S.tune(feats, store=store, kernel="8x4") == BEST


def test_load_records_accepts_bench_payload_and_empty_store(tmp_path):
    """Regression: a downloaded CI artifact dir holds BENCH_spmv.json next
    to the JSONL store -- load_records must read the payload's records list
    (and dedup against the identical JSONL ones), and an empty header-only
    JSONL store must load as zero records, not an error."""
    import json
    st = planted_store(BEST, WORSE)
    st.save_jsonl(str(tmp_path / "spmv_quick.jsonl"))
    payload = {"version": S.RECORDS_VERSION, "mode": "quick", "sections": {},
               "n_records": len(st.records),
               "records": [dataclasses.asdict(r) for r in st.records]}
    with open(tmp_path / "BENCH_spmv.json", "w") as f:
        json.dump(payload, f, indent=1)
    S.RecordStore().save_jsonl(str(tmp_path / "empty.jsonl"))
    merged = S.load_records(str(tmp_path))
    assert merged.records == st.records          # deduped, nothing dropped
    assert S.load_records(str(tmp_path / "BENCH_spmv.json")).records \
        == st.records
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.json"
        bad.write_text('{"not": "a store"}')
        S.load_records(str(bad))


def test_tuned_whole_pick_demoted_with_default_geometry():
    """Regression: when a tuned whole-vector pick exceeds the VMEM budget
    the fallback must use the panel layout's own defaults, not carry the
    whole-layout cb into an unmeasured oversized panel chunk."""
    st = S.RecordStore()
    for avg in (1.0, 3.0, 6.0):
        f = S.MatrixFeatures(0, 0, 0, 4.0, 2.0, avg, avg / 8)
        st.add_measurement("1x8", f, S.PanelConfig("whole", 0, 0, 512), 1, 9.0)
    big = F.csr_to_spc5(matgen.banded(300_000, 4, 1.0, seed=9), 1, 8)
    h = ops.prepare(big, dtype=np.float32, store=st)
    assert h.layout == ops.LAYOUT_PANELS
    assert (h.pr, h.xw, h.cb) == (512, 512, F.PANEL_CB)
    tune_entry = [e for e in h.trace if e["pass"] == "tune"][0]
    assert tune_entry["source"] == "store" and tune_entry["demoted"]


def test_tune_empty_store_falls_back_to_defaults():
    feats = S.MatrixFeatures(0, 0, 0, 5.0, 2.0, 4.0, 0.25)
    assert S.tune(feats, store=S.RecordStore()) == S.DEFAULT_CONFIG
    assert S.tune(feats, store=None) == S.DEFAULT_CONFIG   # no default store
    assert S.DEFAULT_CONFIG.layout == "auto"
    assert (S.DEFAULT_CONFIG.pr, S.DEFAULT_CONFIG.xw) == (512, 512)


def test_prepare_consults_tune_and_honours_overrides():
    csr = matgen.banded(400, 5, 1.0, seed=1)
    mat = F.csr_to_spc5(csr, 2, 8)
    st = planted_store(BEST, WORSE)
    # no store: the pre-tuning default (auto -> whole for a small matrix)
    h0 = ops.prepare(mat, dtype=np.float32)
    assert h0.layout == ops.LAYOUT_WHOLE
    # store passed explicitly: tuned panel config wins
    h1 = ops.prepare(mat, dtype=np.float32, store=st)
    assert h1.layout == ops.LAYOUT_PANELS
    assert (h1.pr, h1.xw, h1.cb) == (16, 32, 8)
    # process-default store: same result with no store argument
    S.set_default_store(st)
    h2 = ops.prepare(mat, dtype=np.float32)
    assert h2.layout == ops.LAYOUT_PANELS and h2.pr == 16
    # explicit arguments are the escape hatch over the tuner
    hw = ops.prepare(mat, dtype=np.float32, layout="whole_vector")
    assert hw.layout == ops.LAYOUT_WHOLE
    assert [e for e in hw.trace if e["pass"] == "tune"][0]["source"] \
        == "explicit"
    assert ops.prepare(mat, dtype=np.float32, layout="panels",
                       pr=48, xw=64).pr == 48
    assert ops.prepare(mat, dtype=np.float32,
                       tune=False).layout == ops.LAYOUT_WHOLE
    # tuned handle computes the right answer
    x = np.random.default_rng(0).standard_normal(400).astype(np.float32)
    y = np.asarray(ops.spmv(h1, jnp.asarray(x), use_pallas=False))
    np.testing.assert_allclose(y, csr.to_dense() @ x, atol=1e-3)


def test_env_var_names_default_store(tmp_path, monkeypatch):
    st = planted_store(BEST, WORSE)
    p = str(tmp_path / "records.jsonl")
    st.save_jsonl(p)
    monkeypatch.setenv(S.RECORDS_ENV, p)
    got = S.get_default_store()
    assert got is not None and len(got.records) == len(st.records)
    mat = F.csr_to_spc5(matgen.banded(400, 5, 1.0, seed=1), 2, 8)
    assert ops.prepare(mat, dtype=np.float32).layout == ops.LAYOUT_PANELS


def test_tuned_config_clamped_to_tiny_matrix():
    """Regression: a store fitted on large matrices proposes pr=2048,
    xw=4096, cb=512 -- prepare must clamp all three to the 8x8 matrix and
    still compute the right product."""
    big_cfg = S.PanelConfig(layout="panels", pr=2048, xw=4096, cb=512)
    st = planted_store(big_cfg, WORSE)
    tiny_csr = matgen.banded(8, 2, 1.0, seed=2)
    tiny = F.csr_to_spc5(tiny_csr, 2, 8)
    h = ops.prepare(tiny, dtype=np.float32, store=st)
    assert h.layout == ops.LAYOUT_PANELS
    assert h.pr <= -(-tiny.nrows // tiny.r) * tiny.r
    assert h.xw <= 2 * 8 + 8               # ncols rounded up + one align
    assert 1 <= h.cb <= max(1, tiny.nblocks)
    x = np.random.default_rng(1).standard_normal(8).astype(np.float32)
    y = np.asarray(ops.spmv(h, jnp.asarray(x), use_pallas=False))
    np.testing.assert_allclose(y, tiny.to_dense() @ x, atol=1e-4)
    # clamp_config itself keeps alignment invariants
    c = S.clamp_config(big_cfg, nrows=8, ncols=8, r=2, c=8, nblocks=4)
    assert c.pr % 2 == 0 and c.xw % 8 == 0 and c.cb >= 1


def test_shard_matrix_tuned_and_explicit_config():
    csr = matgen.banded(1200, 6, 0.8, seed=3)
    mat = F.csr_to_spc5(csr, 1, 8)
    best = S.PanelConfig(layout="panels", pr=64, xw=64, cb=8)
    st = planted_store(best, WORSE, kernel="1x8")
    # tuned: panel shards with the per-shard-clamped config
    sh = D.shard_matrix(mat, 2, store=st)
    assert sh.layout == ops.LAYOUT_PANELS
    assert sh.pr == 64
    # explicit config is the escape hatch
    sh2 = D.shard_matrix(mat, 2,
                         config=S.PanelConfig("whole_vector", 0, 0, 128))
    assert sh2.layout == ops.LAYOUT_WHOLE and sh2.cb == 128
    # no store, no config: the flat default layout, as before
    assert D.shard_matrix(mat, 2, tune=False).layout == ops.LAYOUT_WHOLE
    assert D.shard_matrix(mat, 2).layout == ops.LAYOUT_WHOLE


def test_sweep_records_deterministic():
    """Record identities from the sweep are deterministic run-to-run
    (fixed seeds, fixed candidate grid); only gflops may differ. This is
    what makes `run.py --quick` artifacts comparable across CI runs."""
    sys.path.insert(0, REPO)
    try:
        from benchmarks import bench_spmv_seq as B
    finally:
        sys.path.remove(REPO)
    csr = matgen.banded(200, 4, 1.0, seed=5)
    runs = []
    for _ in range(2):
        st = S.RecordStore()
        lines = B.sweep_matrix("det", csr, st, kernels=((1, 8),),
                               configs=B.SWEEP_CONFIGS, iters=1)
        runs.append((lines, st.records))
    ident = [[{k: v for k, v in dataclasses.asdict(r).items()
               if k != "gflops"} for r in recs] for _, recs in runs]
    assert ident[0] == ident[1]
    names0 = [l.split(",")[0] for l in runs[0][0]]
    names1 = [l.split(",")[0] for l in runs[1][0]]
    assert names0 == names1 and len(names0) > 0


def test_write_artifacts_shape(tmp_path):
    """run.py's artifact writer: BENCH_spmv.json + mergeable JSONL store."""
    import json
    sys.path.insert(0, REPO)
    try:
        from benchmarks import run as bench_run
    finally:
        sys.path.remove(REPO)
    st = planted_store(BEST, WORSE)
    out = str(tmp_path / "BENCH_spmv.json")
    rdir = str(tmp_path / "records")
    bench_run.write_artifacts({"spmv_seq": ["a,1,x"]}, st, out, rdir,
                              mode="quick")
    with open(out) as f:
        payload = json.load(f)
    assert payload["version"] == S.RECORDS_VERSION
    assert payload["mode"] == "quick"
    assert payload["n_records"] == len(st.records) == len(payload["records"])
    assert payload["sections"]["spmv_seq"] == ["a,1,x"]
    merged = S.load_records(rdir)
    assert merged.records == st.records
