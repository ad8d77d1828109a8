"""``panel_chunk_us`` of the SpMM cells: device microseconds per chunk the
panel kernel decodes (layer: Pallas kernel), the summed device time of the
Mosaic custom-call events in the traced window over the ``chunk_steps``
of the window's ``exec.spmm`` spans. A chunk is counted once per vector
tile, so at 8 vectors a grid step it carries the 8 vectors' work.

The window's spans are the last ``len(products)`` ``exec.spmm`` spans of
``repro.obs``'s global registry, one per product. Nothing to read where
the program records fewer, or no ``chunk_steps`` on them."""

SPAN = "exec.spmm"


def window_spans(run):
    """The window's ``exec.spmm`` spans, or None where there are too
    few."""
    products = run.layer.get("products")
    if not products:
        return None
    from repro import obs
    spans = [e for e in obs.get_registry().spans() if e.name == SPAN]
    if len(spans) < len(products):
        return None
    return spans[-len(products):]


def read(run):
    red, spans = run.reduction, window_spans(run)
    if red is None or not spans or red.kernel_s <= 0:
        return None
    chunks = sum(int(e.attrs.get("chunk_steps", 0)) for e in spans)
    if chunks <= 0:
        return None
    return red.kernel_s / chunks * 1e6
