"""Device microseconds per chunk the panel kernel walks (layer: Pallas
kernel): the summed device time of the Mosaic custom-call events in the
traced window over the chunks the window's products walked.

The chunks are the ``chunk_steps`` of the program's own ``exec.spmv``
spans (``panel_step_us.window_spans``): a grid step walks
``chunks_per_step`` of them. Nothing to read where the program records no
such span, or no ``chunk_steps`` on them."""
from bench.harness import load_plugin


def read(run):
    red = run.reduction
    spans = load_plugin("metrics", "panel_step_us").window_spans(run)
    if red is None or not spans or red.kernel_s <= 0:
        return None
    chunks = sum(int(e.attrs.get("chunk_steps", 0)) for e in spans)
    if chunks <= 0:
        return None
    return red.kernel_s / chunks * 1e6
