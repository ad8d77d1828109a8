"""The benchmark's own tests run on the CPU: ``python3 -m pytest bench/tests``
from the checkout's root. Measured runs refuse to start without a TPU;
these tests drive the harness through its CPU rehearsal instead."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
