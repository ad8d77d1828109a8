"""``idle_pct`` of the serving cells."""
from bench.harness import load_plugin


def read(run):
    return load_plugin("metrics", "idle_pct").read(run)
