from repro.kernels.reencode.ops import reencode_rows, reencode_rows_mesh
