"""Compile rehearsals for a TPU v5e: the kernels of the main path, compiled
by the TPU compiler for a described (not attached) chip at real sizes.

Nothing runs here; a compile that passes proves only that Mosaic accepts
the kernel at that size (block shapes, DMA alignment, VMEM and SMEM use).
The plans are passed as ``ShapeDtypeStruct`` leaves, so no matrix is built:
the geometries are those ``ops.prepare`` builds for the two deployments the
chip smoke runs -- a 2M-row banded beta(1,8) matrix and the pruned yi-6b
vocab projection (64000 x 4096) -- and for the benchmark's HPCG stencil.

The topology is described inside a module-scoped fixture (never at import):
only one process may load the TPU library, and each test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import formats as F
from repro.core import plan as P
from repro.kernels import ops

VDTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}

#: name -> (nrows, ncols, chunks per panel, vmax) of a default panels plan
#: (pr = xw = 512, cb = formats.PANEL_CB = 128, beta(1,8)); chunk counts and
#: vmax as ``to_panels`` builds them on a cut of the matrix with the same
#: rows (200,000 banded rows, 4,096 vocab rows, a 104x104x6 stencil slab).
GEOMETRIES = {
    # matgen.banded(2_000_000, 16, 0.75): ~3.6 blocks per row
    "banded_2m": (2_000_000, 2_000_000, 15, 424),
    # matgen.pruned_weight(64000, 4096, 0.05, (1, 8)): ~1.2 nnz per block
    "yi6b_vocab": (64_000, 4_096, 409, 576),
    # the 27-point stencil on 104^3 (hpcg104): 3 blocks per column of a
    # panel, so cb closes every chunk, never xw
    "hpcg104": (1_124_864, 1_124_864, 36, 384),
}

CASES = [
    ("banded_2m", "f32", 1),
    ("banded_2m", "f32", 8),
    ("yi6b_vocab", "f32", 1),
    ("yi6b_vocab", "f32", 8),
    ("banded_2m", "bf16", 1),
    ("yi6b_vocab", "int8", 8),
    ("hpcg104", "f32", 1),
    ("hpcg104", "f32", 8),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _panel_plan(name, vdtype, sharding, pr=512, cb=F.PANEL_CB, xw=512,
                rc=(1, 8)):
    """(plan geometry, plan leaves as ShapeDtypeStructs) of a default
    panels mask plan at a GEOMETRIES size, as beta(rc)."""
    nrows, ncols, nchunks, vmax = GEOMETRIES[name]
    npanels = -(-nrows // pr)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    meta = (npanels, nchunks, cb)
    leaves = [sds((npanels * nchunks * vmax // 2 + vmax,), VDTYPES[vdtype]),
              sds(meta, jnp.int32), sds(meta, jnp.uint32),
              sds(meta, jnp.int32), sds(meta, jnp.int32),
              sds((npanels, nchunks), jnp.int32),
              sds((npanels, nchunks), jnp.int32)]
    if vdtype == "int8":
        leaves.append(sds((npanels, nchunks), jnp.float32))
    geom = dict(r=rc[0], c=rc[1], pr=pr, cb=cb, xw=xw, vmax=vmax,
                npanels=npanels, nchunks=nchunks, nrows=nrows, ncols=ncols,
                ncols_pad=ncols + xw, nnz=0, nblocks=0, vdtype=vdtype)
    return geom, tuple(leaves)


@pytest.fixture
def launched_grids(monkeypatch):
    """The grid of every ``pallas_call`` traced while the test runs."""
    from jax.experimental import pallas as pl
    grids = []
    orig = pl.pallas_call

    def recording(*args, **kw):
        grids.append(tuple(kw["grid"]))
        return orig(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", recording)
    return grids


def _compile_panel_apply(name, vdtype, nvec, one_chip, rc=(1, 8)):
    geom, leaves = _panel_plan(name, vdtype, one_chip, rc=rc)
    xshape = (geom["ncols"],) if nvec == 1 else (geom["ncols"], nvec)
    x = jax.ShapeDtypeStruct(xshape, jnp.float32, sharding=one_chip)

    def run(arrays, x):
        plan = P.SPC5Plan(layout=P.LAYOUT_PANELS, arrays=arrays,
                          meta=tuple(sorted(geom.items())))
        # the executor picks the compiled kernel by itself only on a TPU
        # backend; this host's backend is the CPU, so ask for it
        return plan.apply(x, use_pallas=True, interpret=False)

    compiled = jax.jit(run).lower(leaves, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's stable name, which a trace shows as its op's HLO name
    assert "%spc5_panel_mask" in text
    return geom, compiled.out_info


@pytest.mark.parametrize("name,vdtype,nvec", CASES,
                         ids=[f"{n}-{v}-nvec{k}" for n, v, k in CASES])
def test_panel_mask_kernel_compiles_for_v5e(name, vdtype, nvec, one_chip,
                                            no_persistent_cache,
                                            launched_grids):
    """Each deployment's panels fit one grid step whole: the grid's last
    axis (chunk groups) is 1."""
    geom, out = _compile_panel_apply(name, vdtype, nvec, one_chip)
    assert out.shape == ((geom["nrows"],) if nvec == 1
                         else (geom["nrows"], nvec))
    assert out.dtype == jnp.float32
    assert launched_grids == [(1, geom["npanels"], 1)]


#: Block shapes with r > 1, whose row offsets the kernel's scatter rolls
#: down the y tile; (8, 4) is the tallest supported block.
TALL_BLOCKS = [(2, 4), (4, 8), (8, 4)]


@pytest.mark.parametrize("nvec", [1, 8])
@pytest.mark.parametrize("rc", TALL_BLOCKS,
                         ids=[f"{r}x{c}" for r, c in TALL_BLOCKS])
def test_panel_mask_kernel_compiles_for_v5e_tall_blocks(rc, nvec, one_chip,
                                                        no_persistent_cache):
    geom, out = _compile_panel_apply("banded_2m", "f32", nvec, one_chip, rc)
    assert out.shape == ((geom["nrows"],) if nvec == 1
                         else (geom["nrows"], nvec))


def test_sharded_panel_kernel_compiles_for_a_v5e_mesh(topo,
                                                      no_persistent_cache,
                                                      launched_grids):
    """The sharded plan of the four-chip HPCG deployment (a 208x208x104
    stencil, one 2197-panel slab of 36 chunks per chip), run as
    ``ops.spmv`` runs it: the panel mask kernel under shard_map on each of
    the 2x2 mesh's chips, then the all-gather of y."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    ndev, npanels, nchunks, pr, xw, vmax = 4, 2197, 36, 512, 512, 384
    cb = F.PANEL_CB
    nrows = ncols = 208 * 208 * 104

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    grid = (ndev, npanels, nchunks)
    leaves = ((sds((ndev, npanels * nchunks * vmax // 2 + vmax),
                   jnp.float32, PS("data")),)
              + tuple(sds(grid + (cb,), jnp.int32, PS("data"))
                      for _ in range(4))
              + tuple(sds(grid, jnp.int32, PS("data")) for _ in range(2)))
    geom = dict(r=1, c=8, pr=pr, cb=cb, xw=xw, vmax=vmax,
                rows_max=npanels * pr, nrows=nrows, ncols=ncols,
                ncols_pad=ncols + xw, nnz=0, vdtype="f32")

    def run(arrays, row_start, x):
        sh = P.ShardedPlan(layout=P.LAYOUT_PANELS, arrays=arrays,
                           row_start=row_start,
                           meta=tuple(sorted(geom.items())), mesh=mesh)
        return ops.spmv(sh, x, use_pallas=True, interpret=False)

    compiled = jax.jit(run).lower(
        leaves, sds((ndev,), jnp.int32, PS("data")),
        sds((ncols,), jnp.float32, PS())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%spc5_panel_mask" in text
    assert "all-gather" in text
    assert compiled.out_info.shape == (nrows,)
    # each chip walks a panel's 36 chunks in one grid step
    assert launched_grids == [(1, npanels, 1)]
