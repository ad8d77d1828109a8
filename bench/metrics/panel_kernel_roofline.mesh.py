"""``panel_kernel_roofline`` of the sharded cells (layer: Pallas kernel): per
product of the window, the least time of each shard's product (``flops.
spmv_least_seconds`` of its nnz, rows and touched columns) averaged over
the chips, summed, over the per-chip kernel time of the traced window.
Nothing to read where the products or the shards are unknown; a window
that ran products but holds no Mosaic event is an error, as in
``panel_kernel_roofline``."""
from bench import flops
from bench.harness import BenchError


def read(run):
    red = run.reduction
    products, shards = run.layer.get("products"), run.layer.get("shards")
    if red is None or not products or not shards:
        return None
    if red.kernel_calls == 0 or red.kernel_s <= 0:
        raise BenchError(
            f"the traced window ran {len(products)} products but holds no "
            f"Mosaic custom-call event on a chip ({red.chips} chip plane(s) "
            f"with ops); see python3 -m bench.trace_reduce <trace>")
    least = sum(flops.spmv_least_seconds(
        s["nnz"], s["nrows"], s["ncols"], run.layer["value_bytes"], nvec,
        run.peaks) for nvec in products for s in shards) / len(shards)
    return 100.0 * least / red.kernel_s
