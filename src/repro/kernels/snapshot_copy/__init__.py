from repro.kernels.snapshot_copy.ops import snapshot_copy
from repro.kernels.snapshot_copy.snapshot_copy import dirty_chunks
