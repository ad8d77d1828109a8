"""Host seconds of the plan passes (tune, reorder, layout, build): the sum
of ``duration_s`` over the plan's own ``trace`` (layer: plan passes)."""


def read(run):
    trace = run.layer.get("plan_trace")
    if not trace:
        return None
    return sum(float(e.get("duration_s", 0.0)) for e in trace)
