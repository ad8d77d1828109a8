"""Requests per executed batch over the window, from the server's own
``stats()`` counters (layer: serving tier)."""


def read(run):
    b = run.layer.get("batches")
    if not b or not b["batches"]:
        return None
    return b["requests"] / b["batches"]
