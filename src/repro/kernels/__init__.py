"""Pallas TPU kernels for the APSS engine.

:mod:`repro.kernels.apss_block` is the paper's kernel family: blocked
``X·Yᵀ`` tiles with threshold filtering and tile skipping driven by the
maxweight block-bound mask (partial-indexing/minsize pruning at MXU tile
granularity). ``apss_block.py`` is the seed dense-output kernel,
``fused.py`` the streaming match-extraction kernels, ``sparse.py`` the CSR
tile kernels, ``ops.py`` the jit'd public wrappers and ``ref.py`` the
pure-jnp oracle.

On the CPU the kernels are validated with ``interpret=True`` (Python/CPU
execution of the kernel body); on a TPU v5e they compile with Mosaic
(MXU-aligned 128-multiple tiles, VMEM-sized working sets).
"""

from repro.kernels.apss_block.ops import apss_block_matmul  # noqa: F401
