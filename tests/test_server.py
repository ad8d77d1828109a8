"""Serving tier: plan cache, coalescing parity, config surface, traffic."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import formats as F
from repro.core import matgen
from repro.core import plan as P
from repro.launch import server as SV


def _mat(dim=512, density=0.05, seed=0, rc=(1, 8)):
    csr = matgen.pruned_weight(dim, dim // 2, density, rc, seed=seed)
    return F.csr_to_spc5(csr, *rc)


PANELS = dict(layout="panels", pr=128, xw=32, cb=32, tune=False)


# ----------------------------------------------------------------------------
# PlanCache
# ----------------------------------------------------------------------------

def test_cache_hit_miss_eviction():
    mat = _mat()
    cache = SV.PlanCache(capacity_bytes=1 << 30, verify_on_admit=True)
    p1 = cache.get_or_build(mat, **PANELS)
    assert (cache.hits, cache.misses) == (0, 1)
    assert cache.get_or_build(mat, **PANELS) is p1        # warm: same object
    assert (cache.hits, cache.misses) == (1, 1)
    # a different request is a different plan, not a hit
    p2 = cache.get_or_build(mat, layout="whole_vector", cb=64, tune=False)
    assert p2 is not p1 and cache.misses == 2
    st = cache.stats()
    assert st["entries"] == 2 and st["hit_rate"] == pytest.approx(1 / 3)

    # LRU eviction by plan bytes: capacity for one plan only
    small = SV.PlanCache(capacity_bytes=P.plan_nbytes(p1) + 1)
    small.get_or_build(mat, **PANELS)
    small.get_or_build(mat, layout="whole_vector", cb=64,
                       tune=False)                        # evicts the first
    assert small.evictions >= 1 and len(small) == 1
    small.get_or_build(mat, **PANELS)                     # gone: rebuild
    assert small.hits == 0 and small.misses == 3


def test_cache_verify_on_admission_rejects_corrupt_build():
    mat = _mat()
    good = SV.PlanCache().get_or_build(mat, **PANELS)
    corrupt = dataclasses.replace(
        good, arrays=(jnp.zeros((3,), good.arrays[0].dtype),)
        + good.arrays[1:])              # wrong-shaped values array

    cache = SV.PlanCache(verify_on_admit=True,
                         builder=lambda m, **kw: corrupt)
    from repro.analysis.verify import PlanVerificationError
    with pytest.raises(PlanVerificationError):
        cache.get_or_build(mat, **PANELS)
    assert len(cache) == 0                 # a failed admission caches nothing


def test_fingerprint_stable_and_content_sensitive():
    mat = _mat(seed=1)
    # identical content fingerprints identically, however produced
    clone = F.SPC5Matrix(mat.shape, mat.r, mat.c,
                         mat.block_rowptr.copy(), mat.block_colidx.copy(),
                         mat.block_masks.copy(), mat.block_voffset.copy(),
                         mat.values.copy())
    assert P.matrix_fingerprint(mat) == P.matrix_fingerprint(clone)
    # one edited value changes it
    vals = mat.values.copy()
    vals[0] += 1.0
    edited = F.SPC5Matrix(mat.shape, mat.r, mat.c, mat.block_rowptr,
                          mat.block_colidx, mat.block_masks,
                          mat.block_voffset, vals)
    assert P.matrix_fingerprint(mat) != P.matrix_fingerprint(edited)


def test_cache_key_stable_under_request_permutation():
    mat = _mat(seed=2)
    # spelling the defaults explicitly does not split the cache
    assert P.plan_cache_key(mat) == P.plan_cache_key(
        mat, layout="auto", vdtype="auto", reorder=None, config=None,
        verify=False)
    # keyword ORDER never matters; every decided axis does
    a = P.plan_cache_key(mat, vdtype="bf16", reorder="sigma")
    b = P.plan_cache_key(mat, reorder="sigma", vdtype="bf16")
    assert a == b
    assert a != P.plan_cache_key(mat, vdtype="int8", reorder="sigma")
    assert a != P.plan_cache_key(mat, vdtype="bf16", reorder="rcm")


# ----------------------------------------------------------------------------
# Coalescing parity: batched SpMM bit-identical to per-request SpMV
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["whole_vector", "panels"])
@pytest.mark.parametrize("cb", [32, F.PANEL_CB])
def test_coalesced_spmm_bit_identical(layout, cb):
    mat = _mat(seed=3)
    kw = dict(layout=layout, cb=cb, tune=False)
    if layout == "panels":
        kw.update(pr=128, xw=32)
    cache = SV.PlanCache(verify_on_admit=True)
    plan = cache.get_or_build(mat, **kw)
    rng = np.random.default_rng(4)
    xs = [jnp.asarray(rng.standard_normal(mat.shape[1]), jnp.float32)
          for _ in range(13)]           # odd count: exercises pow2 padding
    with SV.SPC5Server(plan, window_us=20000, max_batch=16) as srv:
        futs = [srv.submit(x) for x in xs]
        ys = [np.asarray(f.result(timeout=60)) for f in futs]
        assert srv.widest_batch > 1     # the batch really coalesced
    for y, x in zip(ys, xs):
        ref = np.asarray(P.execute_spmv(plan, x))
        np.testing.assert_array_equal(y, ref)


def test_single_request_and_closed_server():
    plan = SV.PlanCache().get_or_build(_mat(), **PANELS)
    srv = SV.SPC5Server(plan, window_us=100, max_batch=8)
    x = jnp.ones(dict(plan.meta)["ncols"], jnp.float32)
    y = srv.spmv(x, timeout=60)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(P.execute_spmv(plan, x)))
    srv.close()
    with pytest.raises(RuntimeError):
        srv.submit(x)


# ----------------------------------------------------------------------------
# ServeConfig: one declaration, two consumers
# ----------------------------------------------------------------------------

def test_serve_config_argparse_round_trip():
    import argparse
    ap = argparse.ArgumentParser()
    SV.add_config_args(ap)
    args = ap.parse_args(["--vocab-spmv", "0.05", "--panel", "128,64,32",
                          "--qps", "250", "--cache-mb", "16", "--verify"])
    cfg = SV.config_from_args(args)
    assert cfg.vocab_spmv == 0.05 and cfg.qps == 250 and cfg.verify
    assert cfg.cache_mb == 16
    req = SV.plan_request(cfg)
    assert req == {"layout": "panels", "pr": 128, "xw": 64, "cb": 32,
                   "tune": False, "vdtype": "auto"}
    # defaults produce an all-auto request (nothing splits the cache)
    assert SV.plan_request(SV.ServeConfig()) == {"vdtype": "auto"}


def test_start_builds_server_from_config():
    mat = _mat(seed=5)
    cfg = SV.ServeConfig(panel="128,32,32", window_us=500,
                         max_batch=4, cache_mb=8, verify=True)
    with SV.start(cfg, mat=mat) as srv:
        assert srv.max_batch == 4
        assert srv.cache.stats()["misses"] == 1
        x = jnp.ones(mat.shape[1], jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(srv.spmv(x, timeout=60)),
            np.asarray(P.execute_spmv(srv.plan, x)))
    with pytest.raises(ValueError):
        SV.start(SV.ServeConfig())      # no matrix, vocab_spmv off


# ----------------------------------------------------------------------------
# Open-loop traffic harness
# ----------------------------------------------------------------------------

def test_open_loop_reports_latency_and_throughput():
    plan = SV.PlanCache().get_or_build(_mat(dim=256), layout="panels",
                                       pr=64, xw=16, cb=32, tune=False)
    rng = np.random.default_rng(6)
    xs = [jnp.asarray(rng.standard_normal(dict(plan.meta)["ncols"]),
                      jnp.float32) for _ in range(4)]
    with SV.SPC5Server(plan, window_us=500, max_batch=16) as srv:
        res = SV.open_loop(srv, xs, qps=200, duration_s=0.2, seed=7)
    assert res["completed"] >= 1
    assert res["qps_achieved"] > 0
    assert 0 < res["p50_us"] <= res["p99_us"]
