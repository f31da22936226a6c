"""Pallas TPU kernels: the PIM fixed-function units of Polynesia, re-designed
for the TPU memory hierarchy (HBM -> VMEM -> VREG), plus the LM hot-spots.

Paper unit            -> kernel package        TPU adaptation
---------------------   ---------------------   ------------------------------
sort unit (§5.2)        bitonic_sort            1024-value bitonic network as
                                                lane-rotation/min/max stages
                                                (no gathers), rows in VMEM
merge unit (§5.1)       merge_runs              comparator-tree merge becomes a
                                                bitonic *merge* of run pairs
                                                (data-independent network)
hash lookup unit        hash_probe              pointer-chasing linked buckets
(§5.1/§5.2)                                     become fixed-slot open buckets;
                                                XLA fetches bucket rows, the
                                                kernel compares slots vector-
                                                wide in VMEM
copy unit (§6)          snapshot_copy           fetch/writeback engines become
                                                blocked VMEM-tiled copies with
                                                a dirty-chunk predicate
scan operators (§7)     dict_ops                fused filter->aggregate one-pass
                                                scan over (8, 128) tiles; XLA
                                                decodes the dictionary
LM hot-spots            selective_scan          Mamba-1 recurrence, VMEM state
                        decode_attn             flash-decode w/ online softmax

Every package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd public
wrapper choosing kernel vs reference), ref.py (pure-jnp oracle). Kernels are
checked against the interpret-mode oracle on CPU
(tests/test_kernel_runtime.py), compiled ahead of time for a TPU v5e
(tests/test_tpu_compile.py), and run compiled on the chip by
chip_smoke.py.
"""

from repro.kernels.common import default_interpret
