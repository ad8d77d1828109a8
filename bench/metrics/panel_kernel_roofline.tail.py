"""``panel_kernel_roofline`` of the serving cells: per executed batch, with
the requests the batch carried as its vectors (the zero columns the
coalescer pads with are not work)."""
from bench.harness import load_plugin


def read(run):
    return load_plugin("metrics", "panel_kernel_roofline").read(run)
