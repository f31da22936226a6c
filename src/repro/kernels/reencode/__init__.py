from repro.kernels.reencode.ops import reencode_rows
