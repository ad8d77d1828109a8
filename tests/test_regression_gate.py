"""benchmarks/regression_gate.py edge cases: degraded artifacts must skip
with a note, never crash or false-fail the gate."""
import json
import math

import pytest

from benchmarks import regression_gate as G


def payload(**sections):
    return {"sections": {k: list(v) for k, v in sections.items()}}


def lines(*gflops):
    return [f"bench nnz=100 gflops={g}" for g in gflops]


def test_section_gflops_filters_unparseable_lines():
    p = payload(a=["no measurement here",
                   "bench gflops=nan", "bench gflops=0",
                   "bench gflops=-3.0", "bench gflops=1e999",
                   "bench gflops=2.0"])
    vals = G.section_gflops(p)
    assert vals == {"a": [2.0]}
    assert all(math.isfinite(v) and v > 0 for v in vals["a"])


def test_empty_prior_section_skips(capsys):
    cur = payload(a=lines(*[2.0] * 6))
    pri = payload(a=[])                  # section present but no lines
    assert G.compare(cur, pri) == []
    assert "no prior" in capsys.readouterr().out


def test_all_nan_prior_section_skips(capsys):
    cur = payload(a=lines(*[2.0] * 6))
    pri = payload(a=["bench gflops=nan"] * 6)
    assert G.compare(cur, pri) == []
    assert "no prior" in capsys.readouterr().out


def test_new_current_section_notes_and_passes(capsys):
    # a fresh section (e.g. a new vdtype bench) must skip-with-note, not
    # fail its introducing PR
    cur = payload(a=lines(*[2.0] * 6), fresh_bf16=lines(*[3.0] * 6))
    pri = payload(a=lines(*[2.0] * 6))
    assert G.compare(cur, pri) == []
    out = capsys.readouterr().out
    assert "NEW in the current run" in out and "no prior baseline" in out


def test_prior_only_section_notes_and_passes(capsys):
    cur = payload(a=lines(*[2.0] * 6))
    pri = payload(a=lines(*[2.0] * 6), removed=lines(*[9.0] * 6))
    assert G.compare(cur, pri) == []
    out = capsys.readouterr().out
    assert "'removed' missing in current -- skipped" in out


def test_regression_still_fails():
    cur = payload(a=lines(*[1.0] * 6))
    pri = payload(a=lines(*[2.0] * 6))
    failures = G.compare(cur, pri, threshold=0.25)
    assert len(failures) == 1 and "regressed" in failures[0]


def test_min_lines_skip(capsys):
    cur = payload(a=lines(1.0, 1.0))
    pri = payload(a=lines(9.0, 9.0))
    assert G.compare(cur, pri, min_lines=5) == []
    assert "<5 lines" in capsys.readouterr().out


def test_main_exit_codes(tmp_path):
    cur, pri = tmp_path / "cur.json", tmp_path / "pri.json"
    cur.write_text(json.dumps(payload(a=lines(*[2.0] * 6))))
    pri.write_text(json.dumps(payload(a=lines(*[2.0] * 6),
                                      gone=lines(*[5.0] * 6))))
    assert G.main(["--current", str(cur), "--prior", str(pri)]) == 0
    pri.write_text(json.dumps(payload(a=lines(*[9.0] * 6))))
    assert G.main(["--current", str(cur), "--prior", str(pri)]) == 1


@pytest.mark.parametrize("bad", ["nan", "inf", "-inf", "none", ""])
def test_degenerate_gflops_values_do_not_crash(bad):
    p = payload(a=[f"bench gflops={bad}"] * 6)
    assert G.compare(p, p) == []
    assert G.compare(payload(a=lines(*[2.0] * 6)), p) == []


def test_regression_gate_compare():
    def scaled(scale):
        return {"sections": {
            "spmv_seq": [f"spmv_seq.m.k{i},1.0,gflops={scale * (1 + i)}"
                         for i in range(6)],
            "tiny": ["tiny.x,1.0,gflops=1.0"],          # < min_lines: skip
        }}

    assert G.section_gflops(scaled(1.0))["spmv_seq"] == [1.0, 2.0, 3.0, 4.0,
                                                        5.0, 6.0]
    # same perf: pass
    assert G.compare(scaled(1.0), scaled(1.0)) == []
    # 10% faster: pass; 50% slower: fail; new section with no prior: skip
    assert G.compare(scaled(1.1), scaled(1.0)) == []
    failures = G.compare(scaled(0.5), scaled(1.0))
    assert len(failures) == 1 and "spmv_seq" in failures[0]
    cur = scaled(0.5)
    cur["sections"]["brand_new"] = ["brand_new.x,1,gflops=1"] * 6
    assert len(G.compare(cur, scaled(1.0))) == 1    # new section skipped
    # within threshold (20% drop < 25%): pass
    assert G.compare(scaled(0.8), scaled(1.0)) == []
