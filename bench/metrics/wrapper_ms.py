"""Device milliseconds per product of the library's ops other than the
kernel (layer: executor and wrapper copies): value pad and reshape,
metadata stack, x pad, output reshape. The benchmark's own programs
(``jit_bench_*``) are left out."""


def read(run):
    red, products = run.reduction, run.layer.get("products")
    if red is None or not red.chips or not products:
        return None
    return red.library_s / len(products) * 1e3
