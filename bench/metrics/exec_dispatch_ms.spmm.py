"""``exec_dispatch_ms`` of the SpMM cells: host milliseconds per product in
the library's executor (layer: executor, host dispatch), the mean
duration of the window's ``exec.spmm`` spans (see ``panel_chunk_us.spmm.
window_spans``). Nothing to read where the program records too few."""
from bench.harness import load_plugin


def read(run):
    spans = load_plugin("metrics", "panel_chunk_us.spmm").window_spans(run)
    if not spans:
        return None
    return sum(e.duration_s for e in spans) / len(spans) * 1e3
