"""Distributed tests on 8 fake devices (subprocess keeps main at 1 device)."""
import os

import pytest


def test_distributed_spmv_allclose(devices8):
    devices8("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import formats as F, distributed as D, matgen
csr = matgen.banded(1200, 6, 0.8, seed=3)
d = csr.to_dense()
for rc in [(1, 8), (4, 4)]:
    mat = F.csr_to_spc5(csr, *rc)
    mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
    sh = D.shard_matrix(mat, 8, cb=64, mesh=mesh)
    run = D.make_distributed_spmv(sh, mesh)
    x = np.random.default_rng(0).standard_normal(1200).astype(np.float32)
    y = np.asarray(run(jnp.asarray(x)))
    tgt = d @ x
    rel = np.abs(y - tgt).max() / (np.abs(tgt).max() + 1e-9)
    assert rel < 1e-5, (rc, rel)
print("OK")
""")


def test_distributed_spmv_sharded_output(devices8):
    devices8("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import formats as F, distributed as D, matgen
csr = matgen.fem_blocks(640, 4, 5, seed=4)
mat = F.csr_to_spc5(csr, 2, 4)
mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
sh = D.shard_matrix(mat, 8, cb=32, mesh=mesh)
run = D.make_distributed_spmv(sh, mesh, gather=False)
x = np.random.default_rng(1).standard_normal(sh.ncols).astype(np.float32)
slabs = np.asarray(run(jnp.asarray(x)))   # (8, rows_max) row slabs
assert slabs.shape[0] == 8
# reassemble on host
starts = np.asarray(sh.row_start)
y = np.zeros(sh.nrows + sh.rows_max)
for i, r0 in enumerate(starts):
    y[r0:r0+sh.rows_max] += slabs[i]
tgt = csr.to_dense() @ x
rel = np.abs(y[:sh.nrows] - tgt).max() / (np.abs(tgt).max() + 1e-9)
assert rel < 1e-5, rel
print("OK")
""")


def test_compressed_psum_grad_allreduce(devices8):
    devices8("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.optim.compress import compressed_psum
mesh = Mesh(np.array(jax.devices()).reshape(8,), ("dp",))
g_global = np.random.default_rng(0).standard_normal((8, 64, 32)).astype(np.float32)

def body(g):
    red, res = compressed_psum({"w": g[0]}, "dp")
    return red["w"][None]

fn = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
                   check_vma=False)
out = np.asarray(jax.jit(fn)(g_global))
tgt = g_global.mean(axis=0)
# shared-scale int8: per-device rounding err <= s/2; averaged over n the
# worst case stays <= s/2 (errors can align), s = rowmax/127
err = np.abs(out[0] - tgt).max()
scale = np.abs(g_global).max() / 127.0
assert err < scale * 0.75, (err, scale)
print("OK")
""")


def test_sharding_rules_on_mesh(devices8):
    devices8("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.launch.mesh import make_test_mesh
from repro.sharding.rules import make_rules
from repro.configs import get_smoke_config
from repro.models import model as MD
mesh = make_test_mesh((2, 4), ("data", "model"))
rules = make_rules(mesh)
cfg = get_smoke_config("glm4-9b")
params_s = jax.eval_shape(lambda: MD.init_params(cfg, jax.random.PRNGKey(0)))
shardings = rules.param_shardings(params_s)
# every leaf gets a sharding; matrices use the mesh
leaves = jax.tree.leaves(shardings)
assert all(l is not None for l in leaves)
# opt shardings never error
_ = rules.opt_shardings(params_s)
print("OK", len(leaves))
""")


def test_tiny_sharded_train_step(devices8):
    devices8("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_test_mesh
from repro.sharding.rules import make_rules
from repro.configs import get_smoke_config
from repro.models import model as MD
from repro.models.config import ShapeConfig
from repro.train.step import make_train_step
from repro.optim import AdamWConfig, adamw_init
from repro.data.synthetic import SyntheticLM

mesh = make_test_mesh((2, 4), ("data", "model"))
rules = make_rules(mesh, fsdp=True)
cfg = get_smoke_config("yi-6b")
shape = ShapeConfig("t", 64, 4, "train")
params = MD.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params)
step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3), rules, "nothing"))
data = SyntheticLM(cfg, 64, 4)
l0 = None
for i in range(4):
    batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
    params, opt, m = step(params, opt, batch)
    if l0 is None: l0 = float(m["loss"])
lN = float(m["loss"])
assert np.isfinite(lN) and lN < l0 + 0.5, (l0, lN)
print("OK", l0, lN)
""")


def test_multipod_mesh_construction(devices8):
    # 8 devices can't build the production mesh; check the error message and
    # the small-mesh path instead
    devices8("""
from repro.launch.mesh import make_production_mesh, make_test_mesh
try:
    make_production_mesh()
    raise SystemExit("should have raised")
except RuntimeError as e:
    assert "512" in str(e) or "256" in str(e)
m = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
assert m.shape == {"pod": 2, "data": 2, "model": 2}
print("OK")
""")


def test_tuned_lowerings_survive_workers(devices8):
    # descriptor (and every tuned lowering) must survive workers=ndev: the
    # old shard path silently demoted descriptor requests to mask
    devices8("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import formats as F, distributed as D, matgen
from repro.core import plan as P
csr = matgen.banded(1024, 6, 0.7, seed=5)
d = csr.to_dense()
mat = F.csr_to_spc5(csr, 1, 8)
mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
x = np.random.default_rng(0).standard_normal(1024).astype(np.float32)
for layout, kw in [("whole_vector", dict(cb=64)),
                   ("panels", dict(pr=256, cb=32))]:
    for lowering in ("mask", "descriptor"):
        sh = D.shard_matrix(mat, 8, mesh=mesh, layout=layout,
                            lowering=lowering, **kw)
        served = [e for e in sh.trace if e.get("pass") == "lowering"]
        assert served and served[0]["lowering"] == lowering, sh.trace
        assert served[0]["reason"] == "requested", sh.trace
        assert not any(k.endswith("demoted") for e in sh.trace for k in e)
        y = np.asarray(D.make_distributed_spmv(sh, mesh)(jnp.asarray(x)))
        tgt = d @ x
        rel = np.abs(y - tgt).max() / (np.abs(tgt).max() + 1e-9)
        assert rel < 1e-5, (layout, lowering, rel)
print("OK")
""")


def test_nnz_balanced_partition_on_devices(devices8):
    # a skewed matrix: nnz-balancing must shrink the heaviest shard's share
    # vs block-count balancing, and both must stay correct end to end
    devices8("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import formats as F, distributed as D, matgen, partition as PT
csr = matgen.powerlaw(1536, 12, alpha=1.6, seed=2)
d = csr.to_dense()
mat = F.csr_to_spc5(csr, 1, 8)
mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
x = np.random.default_rng(3).standard_normal(1536).astype(np.float32)
skews = {}
for mode in ("blocks", "nnz"):
    sh = D.shard_matrix(mat, 8, cb=64, mesh=mesh, lowering="mask",
                        partition=mode)
    part = [e for e in sh.trace if e.get("pass") == "partition"][0]
    assert part["mode"] == mode, sh.trace
    skews[mode] = PT.nnz_skew(mat, 8, mode)
    y = np.asarray(D.make_distributed_spmv(sh, mesh)(jnp.asarray(x)))
    tgt = d @ x
    rel = np.abs(y - tgt).max() / (np.abs(tgt).max() + 1e-9)
    assert rel < 1e-5, (mode, rel)
assert skews["nnz"] <= skews["blocks"], skews
print("OK")
""")


def test_parallel_bench_runs_in_process(devices8):
    """The Fig. 4 bench drives every device of the calling process -- no
    child process competes for the devices."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = devices8(f"""
import sys
sys.path.insert(0, {repo!r})
from benchmarks import bench_spmv_par
from repro.core.selector import RecordStore
store = RecordStore()
lines = bench_spmv_par.run(names=["rajat31"], store=store)
assert len(lines) == 2 and all("_dev8" in l for l in lines), lines
assert {{r.workers for r in store.records}} == {{8}}
print("OK")
""")
    assert "OK" in out
