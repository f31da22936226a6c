"""CPU tests of the benchmark: ``python -m pytest chipbench/tests``."""

import os

# the benchmark's tests run on the CPU; the chip is for the benchmark
os.environ.setdefault("JAX_PLATFORMS", "cpu")
