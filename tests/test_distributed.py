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
    # a requested layout survives workers=ndev: the shard entry names it,
    # nothing is demoted, and the sharded product is right
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
    sh = D.shard_matrix(mat, 8, mesh=mesh, layout=layout, **kw)
    served = [e for e in sh.trace if e.get("pass") == "shard"]
    assert served and served[0]["layout"] == layout, sh.trace
    assert served[0]["cb"] == kw["cb"], sh.trace
    assert not any(k.endswith("demoted") for e in sh.trace for k in e)
    y = np.asarray(D.make_distributed_spmv(sh, mesh)(jnp.asarray(x)))
    tgt = d @ x
    rel = np.abs(y - tgt).max() / (np.abs(tgt).max() + 1e-9)
    assert rel < 1e-5, (layout, rel)
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
    sh = D.shard_matrix(mat, 8, cb=64, mesh=mesh, partition=mode)
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


# ----------------------------------------------------------------------------
# The sharded plan behind ops.spmv, on 4 devices: the Pallas panel kernel on
# every device's slab (interpret mode here), y all-gathered
# ----------------------------------------------------------------------------

SHARDED_PANEL_RUNS = """
import json, re, sys
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS
sys.path.insert(0, {repo!r})
from bench.gen import hpcg_stencil
from repro import obs
from repro.core import formats as F, distributed as D
from repro.kernels import ops
shape, rowptr, colidx, values = hpcg_stencil.generate(
    dict(nx=16, ny=12, nz=8), 0)
csr = F.CSRMatrix(tuple(shape), rowptr, colidx, values)
mesh = Mesh(np.asarray(jax.devices()), ("data",))
x = np.random.default_rng(7).standard_normal(shape[1]).astype(np.float32)
xr = jax.device_put(x, NamedSharding(mesh, PS()))
out, spans = {{"x": x, "dense": csr.to_dense()}}, {{}}
for r, c in ((1, 8), (2, 4)):
    sh = D.shard_matrix(F.csr_to_spc5(csr, r, c), 4, mesh=mesh,
                        layout="panels", pr=64, cb=16)
    before = len(obs.get_registry().spans())
    out[f"y{{r}}x{{c}}"] = np.asarray(
        ops.spmv(sh, xr, use_pallas=True, interpret=True))
    spans[f"{{r}}x{{c}}"] = dict(
        names=[e.name for e in obs.get_registry().spans()[before:]],
        attrs=obs.get_registry().spans()[-1].attrs,
        shard_chunks=sh.chunk_vbase.shape[1:])
    prog = sh.program(use_pallas=True, interpret=True)
    text = prog.func.lower(*prog.args, xr).as_text()
    spans[f"{{r}}x{{c}}"].update(
        plan_bytes=sum(int(a.nbytes) for a in sh.arrays),
        largest_constant=max(map(len, re.findall(r"dense<[^>]*>", text))))
    slabs = np.asarray(D.make_distributed_spmv(
        sh, mesh, gather=False, use_pallas=True, interpret=True)(xr))
    y = np.zeros(sh.nrows + sh.rows_max)
    for k, r0 in enumerate(np.asarray(sh.row_start)):
        y[r0:r0 + sh.rows_max] += slabs[k]
    out[f"slabs{{r}}x{{c}}"] = y[:sh.nrows]
np.savez({path!r}, **out)
print("SPANS " + json.dumps(spans))
"""


@pytest.fixture(scope="module")
def sharded_panel_runs(devices4, tmp_path_factory):
    """ops.spmv and make_distributed_spmv(gather=False) on a 16x12x8 HPCG
    stencil sharded over 4 devices, as beta(1,8) and beta(2,4) panels with
    several panels and chunks per slab."""
    import json

    import numpy as np
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = str(tmp_path_factory.mktemp("sharded") / "runs.npz")
    out = devices4(SHARDED_PANEL_RUNS.format(repo=repo, path=path))
    spans = json.loads(out.split("SPANS ", 1)[1])
    return dict(np.load(path)), spans


@pytest.mark.parametrize("form", ["y", "slabs"])
@pytest.mark.parametrize("rc", ["1x8", "2x4"])
def test_sharded_panel_kernel_matches_float64(sharded_panel_runs,
                                              rowwise_close, rc, form):
    """The gathered ``ops.spmv`` answer and the row slabs of
    ``gather=False``, reassembled, both within a float32 reassociation of
    each row of the float64 product."""
    arrays, _ = sharded_panel_runs
    rowwise_close(arrays[form + rc], arrays["dense"], arrays["x"], 1e-5)


@pytest.mark.parametrize("rc", ["1x8", "2x4"])
def test_sharded_dispatch_records_one_exec_span(sharded_panel_runs, rc):
    """One ``exec.spmv`` span per sharded product, no nested one, with the
    mesh width and the grid steps one device launches."""
    _, spans = sharded_panel_runs
    s = spans[rc]
    assert [n for n in s["names"] if n.startswith(("exec.", "distributed."))
            ] == ["exec.spmv"]
    assert s["attrs"]["ndev"] == 4
    assert s["attrs"]["layout"] == "panels" and s["attrs"]["lowering"] == \
        "mask"
    npanels, nchunks = s["shard_chunks"]
    assert npanels > 4 and nchunks > 1
    # a grid step walks a whole panel's chunks at this size
    assert s["attrs"]["grid_steps"] == npanels
    assert s["attrs"]["chunk_steps"] == npanels * nchunks
    assert s["attrs"]["chunks_per_step"] == nchunks
    assert s["attrs"]["vectors_per_step"] == 1


@pytest.mark.parametrize("rc", ["1x8", "2x4"])
def test_sharded_program_takes_the_plan_as_arguments(sharded_panel_runs, rc):
    """The program ``ops.spmv`` runs on a sharded plan holds no copy of the
    matrix: its largest constant is a small fraction of the plan's bytes
    (closed over instead, jit bakes every slab into the executable)."""
    s = sharded_panel_runs[1][rc]
    assert s["plan_bytes"] > 100_000
    assert s["largest_constant"] < s["plan_bytes"] // 100


def test_shard_matrix_on_a_tpu_picks_the_mosaic_kernel(monkeypatch):
    """On a TPU backend the shard pass applies the plan pass's rule: "auto"
    resolves to the panels mask kernel, the skipped default traced, and an
    explicit request without a Mosaic kernel raises."""
    from repro.core import distributed as D
    from repro.core import formats as F
    from repro.core import matgen
    from repro.core import plan as P
    mat = F.csr_to_spc5(matgen.banded(512, 4, 1.0, seed=5), 1, 8)
    assert D.shard_matrix(mat, 4).layout == "whole_vector"
    monkeypatch.setattr(P, "_on_tpu", lambda: True)
    sh = D.shard_matrix(mat, 4)
    assert (sh.layout, sh.lowering) == ("panels", "mask")
    entry = next(e for e in sh.trace if e["pass"] == "shard")
    assert entry["layout"] == "panels"
    assert entry["layout_demoted"] is True
    assert entry["layout_demoted_reason"] == "no-mosaic-kernel:whole_vector"
    for request in (dict(layout="whole_vector"), dict(layout="test")):
        with pytest.raises(ValueError, match="compiles for a TPU"):
            D.shard_matrix(mat, 4, **request)


def test_sharded_plan_without_a_mesh_refuses_ops_spmv():
    import jax.numpy as jnp

    from repro.core import distributed as D
    from repro.core import formats as F
    from repro.core import matgen
    from repro.kernels import ops
    sh = D.shard_matrix(F.csr_to_spc5(matgen.banded(256, 4, 1.0), 1, 8), 2)
    with pytest.raises(ValueError, match="without a mesh"):
        ops.spmv(sh, jnp.ones(256))
