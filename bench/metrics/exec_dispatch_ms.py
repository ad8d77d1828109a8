"""Host milliseconds per product spent in the library's executor (layer:
executor, host dispatch): the mean duration of the window's
``exec.spmv`` spans, which cover the dispatch of each product and not
its device time. Nothing to read where the program records no such span
(see ``panel_step_us.window_spans``)."""
from bench.harness import load_plugin


def read(run):
    spans = load_plugin("metrics", "panel_step_us").window_spans(run)
    if not spans:
        return None
    return sum(e.duration_s for e in spans) / len(spans) * 1e3
