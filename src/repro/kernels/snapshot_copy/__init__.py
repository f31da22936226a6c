from repro.kernels.snapshot_copy.ops import snapshot_copy, snapshot_copy_mesh
from repro.kernels.snapshot_copy.snapshot_copy import dirty_chunks
