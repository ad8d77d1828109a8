"""The panel kernel's share of its roofline, in percent (layer: Pallas
kernel).

The least time of each product the window ran (``flops.
spmv_least_seconds``: the values once, x and y once per vector, at the
chip's published HBM bandwidth), summed, over the summed device time of
the Mosaic custom-call events in the traced window. Nothing to read where
the products are unknown. A window that ran products but holds no Mosaic
event is an error, not a missing reading: the reduction no longer finds
the kernel, and its time would be counted as the wrapper's."""
from bench import flops
from bench.harness import BenchError


def read(run):
    red, products = run.reduction, run.layer.get("products")
    if red is None or not products:
        return None
    if red.kernel_calls == 0 or red.kernel_s <= 0:
        raise BenchError(
            f"the traced window ran {len(products)} products but holds no "
            f"Mosaic custom-call event on a chip ({red.chips} chip plane(s) "
            f"with ops); see python3 -m bench.trace_reduce <trace>")
    nrows, ncols = run.layer["shape"]
    least = sum(flops.spmv_least_seconds(
        run.layer["nnz"], nrows, ncols, run.layer["value_bytes"], nvec,
        run.peaks) for nvec in products)
    return 100.0 * least / red.kernel_s
