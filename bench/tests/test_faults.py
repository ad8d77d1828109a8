"""A run with the timed path broken underneath must read incorrect.

Each fault wraps the library's executors (``plan.execute_spmv`` /
``execute_spmm``), which both cells' timed paths go through, and the rest
of the run is the harness's own, on the CPU rehearsal. The exchange
between chips does not exist in these one-chip cells."""
import jax.numpy as jnp
import pytest

from test_harness import rehearse


def altered_answer(spmv, spmm, csr):
    """One entry of every answer changed where it is produced."""
    def bump(y):
        return y.at[(y.shape[0] // 2,) + (0,) * (y.ndim - 1)].add(1.0)
    return (lambda *a, **kw: bump(spmv(*a, **kw)),
            lambda *a, **kw: bump(spmm(*a, **kw)))


def state_unchanged(spmv, spmm, csr):
    """A product that hands back its input instead of A @ x."""
    def same(plan, x, **kw):
        y = spmv(plan, x, **kw) if x.ndim == 1 else spmm(plan, x, **kw)
        return jnp.zeros_like(y) + x[: y.shape[0]] if \
            x.shape[0] >= y.shape[0] else jnp.zeros_like(y)
    return same, same


def half_batch_left_out(spmv, spmm, csr):
    """The second half of each batch's columns dropped (zeros)."""
    def half(plan, x, **kw):
        y = spmm(plan, x, **kw)
        keep = jnp.arange(y.shape[1]) < max(1, y.shape[1] // 2)
        return jnp.where(keep[None, :], y, 0.0)
    return spmv, half


def answers_swapped(spmv, spmm, csr):
    """Each batch's answers handed to the wrong requests."""
    return spmv, lambda *a, **kw: jnp.roll(spmm(*a, **kw), 1, axis=1)


@pytest.mark.parametrize("cell,fault", [
    ("hpcg104.cg", altered_answer),
    ("hpcg104.cg", state_unchanged),
    ("yi6b_vocab.tail", altered_answer),
    ("yi6b_vocab.tail", state_unchanged),
    ("yi6b_vocab.tail", half_batch_left_out),
    ("yi6b_vocab.tail", answers_swapped),
])
def test_fault_reads_incorrect(cell, fault):
    res = rehearse(cell, wrap=fault, seconds=3.0)
    assert not res["correct"], (fault.__name__, res["checks"])
