"""The benchmark's own tests run on the CPU, Pallas in interpret mode:
    JAX_PLATFORMS=cpu python -m pytest qbench/tests -q"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
