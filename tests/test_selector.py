"""Kernel-selection tests (paper §Performance prediction)."""
import json
import os

import numpy as np
import pytest

from repro.core import formats as F
from repro.core import matgen, selector as S


def test_record_store_roundtrip(tmp_path):
    p = str(tmp_path / "records.json")
    store = S.RecordStore(p)
    store.add("4x8", 12.0, 1, 3.5, matrix="m1")
    store.add("1x8", 2.0, 8, 1.5)
    store.save()
    store2 = S.RecordStore(p)
    assert len(store2.records) == 2
    assert store2.records[0].kernel == "4x8"
    assert store2.kernels() == ["1x8", "4x8"]


def test_sequential_predictor_recovers_law():
    """If gflops = a + b*avg the polyfit must recover it."""
    store = S.RecordStore()
    for avg in [1, 2, 4, 8, 16, 24]:
        store.add("4x8", avg, 1, 0.5 + 0.1 * avg)
        store.add("1x8", avg, 1, 1.0 + 0.01 * avg)
    pred = S.SequentialPredictor(store)
    assert pred.predict("4x8", 10.0) == pytest.approx(1.5, rel=1e-3)
    assert pred.predict("1x8", 10.0) == pytest.approx(1.1, rel=1e-3)
    # crossover: low fill prefers 1x8, high fill prefers 4x8
    assert pred.predict("1x8", 2.0) > pred.predict("4x8", 2.0)
    assert pred.predict("4x8", 24.0) > pred.predict("1x8", 24.0)


def test_sequential_predictor_clamps_extrapolation():
    """Regression: queries outside the fitted Avg range must clamp to the
    range edge, not extrapolate the polynomial (a downward-curving degree-2
    fit would otherwise predict -inf-ish throughput far outside the data and
    an upward-curving one would fabricate wins)."""
    store = S.RecordStore()
    # concave fit: peak inside the fitted range, plummets outside it
    for avg in [2.0, 4.0, 6.0, 8.0, 10.0]:
        store.add("4x8", avg, 1, 10.0 - (avg - 6.0) ** 2)
    pred = S.SequentialPredictor(store)
    assert pred.clip["4x8"] == (2.0, 10.0)
    # clamped: far-out queries return the edge prediction, not the raw poly
    assert pred.predict("4x8", 1000.0) == pytest.approx(pred.predict("4x8", 10.0))
    assert pred.predict("4x8", -50.0) == pytest.approx(pred.predict("4x8", 2.0))
    # unclamped polynomial would be catastrophically wrong
    raw = float(np.polyval(pred.coeffs["4x8"], 1000.0))
    assert raw < -900_000
    # predictions stay bounded by the fitted data's scale
    assert abs(pred.predict("4x8", 1000.0)) <= 11.0


def test_record_store_pr_field_roundtrip(tmp_path):
    p = str(tmp_path / "records.json")
    store = S.RecordStore(p)
    store.add("4x8", 12.0, 1, 3.5, matrix="m1", pr=512)
    store.add("4x8", 12.0, 1, 3.1)   # whole-vector layout -> pr defaults to 0
    store.save()
    store2 = S.RecordStore(p)
    assert [r.pr for r in store2.records] == [512, 0]


def test_parallel_predictor_2d():
    store = S.RecordStore()
    for avg in [1.0, 4.0, 16.0]:
        for w in [1, 4, 16, 52]:
            store.add("2x4", avg, w, 0.2 * avg + 0.5 * np.log2(w) + 1.0)
    pred = S.ParallelPredictor(store)
    got = pred.predict("2x4", 8.0, 8)
    assert got == pytest.approx(0.2 * 8 + 0.5 * 3 + 1.0, rel=0.05)


def test_select_kernel_end_to_end():
    csr = matgen.fem_blocks(400, 4, 6, seed=1)
    store = S.RecordStore()
    # synthetic records: large blocks win at high fill
    for k in S.DEFAULT_KERNELS:
        r, c = S.kernel_block(k)
        for avg in [1.0, 4.0, 12.0, 30.0]:
            store.add(k, avg, 1, avg * (r * c) ** 0.25)
    best, score, scores = S.select_kernel(csr, store, workers=1)
    assert best in S.DEFAULT_KERNELS
    assert score == max(scores.values())
    feats = S.matrix_features(csr)
    assert set(feats) == set(S.DEFAULT_KERNELS)
    # fem 4x4 blocks: beta(4,4) should be well filled
    assert feats["4x4"] > F.beta_breakeven_avg(4, 4)


def test_selector_empty_store_graceful():
    csr = matgen.banded(100, 3, 1.0)
    best, score, _ = S.select_kernel(csr, S.RecordStore(), workers=1)
    assert best in S.DEFAULT_KERNELS  # -inf everywhere, max returns a kernel


def _write_jsonl(path, version, records):
    with open(path, "w") as f:
        f.write(json.dumps({"spc5_records_version": version}) + "\n")
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_record_store_v1_v2_v3_roundtrip(tmp_path):
    """v1/v2/v3 stores load; a v3 record measured on the mask decode pools
    with the legacy ones, and one measured on the removed descriptor
    lowering is dropped and counted as skipped."""
    base = dict(kernel="1x8", avg=4.0, workers=1, gflops=2.0, matrix="m",
                pr=0, xw=0, cb=512, layout="whole_vector", nnz_row=5.0,
                bandwidth=2.0, fill=0.5)
    v1 = {k: v for k, v in base.items()
          if k not in ("layout",)} | {"layout": "whole"}   # legacy spelling
    v2 = base | {"reorder": "rcm", "bandwidth_post": 1.0, "nchunks": 3}
    v3 = base | {"gflops": 3.0, "reorder": "", "bandwidth_post": 0.0,
                 "nchunks": 0, "lowering": "mask"}
    v3_dropped = dict(v3, lowering="descriptor")
    _write_jsonl(tmp_path / "v1.jsonl", 1, [v1])
    _write_jsonl(tmp_path / "v2.jsonl", 2, [v2])
    _write_jsonl(tmp_path / "v3.jsonl", 3, [v3, v3_dropped])
    with pytest.warns(UserWarning, match="removed 'descriptor' lowering"):
        store = S.load_records(str(tmp_path))
    assert len(store.records) == 3 and store.skipped == 1
    # the v1 and v3 mask records are one configuration
    cfgs = {r.config() for r in store.records}
    assert cfgs == {S.PanelConfig("whole_vector", 0, 0, 512),
                    S.PanelConfig("whole_vector", 0, 0, 512,
                                  reorder="rcm")}
    # round-trip through save_jsonl stamps the current version
    out = tmp_path / "out" / "out.jsonl"
    out.parent.mkdir()
    store.save_jsonl(str(out))
    with open(out) as f:
        head = json.loads(f.readline())
    assert head["spc5_records_version"] == S.RECORDS_VERSION == 4
    store2 = S.RecordStore(str(out))
    assert store2.records == store.records and store2.skipped == 0
    # a store claiming a NEWER version than supported refuses to load
    _write_jsonl(tmp_path / "v9.jsonl", 9, [v3])
    with pytest.raises(ValueError):
        S._load_jsonl(str(tmp_path / "v9.jsonl"))
