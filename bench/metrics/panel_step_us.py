"""Device microseconds per grid step of the panel kernel (layer: Pallas
kernel): the summed device time of the Mosaic custom-call events in the
traced window over the grid steps the window's products launched.

The steps are the ``grid_steps`` of the program's own ``exec.spmv``
spans: the last ``len(products)`` that ``repro.obs``'s global registry
holds, one per product of the window. Nothing to read where the program
records no such span, or fewer than the window ran."""


def window_spans(run):
    """The window's ``exec.spmv`` spans, or None where there are too
    few."""
    products = run.layer.get("products")
    if not products:
        return None
    from repro import obs
    spans = [e for e in obs.get_registry().spans() if e.name == "exec.spmv"]
    if len(spans) < len(products):
        return None
    return spans[-len(products):]


def read(run):
    red, spans = run.reduction, window_spans(run)
    if red is None or not spans or red.kernel_s <= 0:
        return None
    steps = sum(int(e.attrs.get("grid_steps", 0)) for e in spans)
    if steps <= 0:
        return None
    return red.kernel_s / steps * 1e6
