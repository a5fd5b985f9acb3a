"""repro.kernels — Pallas TPU kernels for the compute hot-spots.

Each kernel is a subpackage: ``kernel.py`` (pl.pallas_call + BlockSpec VMEM
tiling), ``ops.py`` (jit'd public wrapper with the interpret/TPU switch),
``ref.py`` (pure-jnp oracle).  Kernels are validated on CPU via
``interpret=True`` (the kernel body executes in Python) and tiled for the
TPU v5e memory hierarchy: blocks sized to fit the scoped VMEM the kernels
are compiled under (``tuning.VMEM_LIMIT_BYTES``) with MXU-aligned
(128x128) matmul dims; tests/test_tpu_compile.py compiles each for a v5e.

SCOPE mapping: the paper's TCU|Scope measures Nvidia tensor cores; our
matmul kernel is the MXU analogue (mxu_scope's measured body).  Histo|Scope
maps to the histogram kernel.  cuDNN|Scope's NN-op bodies map to
flash_attention / rmsnorm / ssd_scan.
"""
