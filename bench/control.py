"""The control of the check: the plain reference put in the program's
place, computed one precision below what the configurations state.

The configurations state float32, so the control stores the values and
the vectors in bfloat16 and accumulates in bfloat16. HPCG's values (26 and
-1) are exact in bfloat16, so there the control's error comes from the
vectors and the sums alone. A check that lets the control pass cannot
tell a float32 product from a bfloat16 one, and its limit is wrong.
"""
from __future__ import annotations

import jax.numpy as jnp


def bf16_product(spmv, spmm, csr):
    """``wrap_product`` for :func:`bench.harness.run_cell`: both of the
    library's executors answered by the bfloat16 reference."""
    from bench.reference import CSRReference
    del spmv, spmm
    ref = CSRReference(*csr, dtype=jnp.bfloat16)

    def execute(plan, x, **kw):
        del plan, kw
        return ref.apply(x)

    return execute, execute
