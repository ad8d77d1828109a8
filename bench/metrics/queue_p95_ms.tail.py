"""95th percentile over the window's requests of the time from when a
request was due to the start of the ``serve.batch`` span that carried it,
in ms (layer: serving tier). A request no batch carried counts as
infinitely late."""
import math

from bench import openloop


def read(run):
    waits = run.layer.get("queue_waits_s")
    if not waits:
        return None
    waits = [math.inf if math.isnan(w) else w for w in waits]
    return openloop.percentile(waits, 95) * 1e3
