"""Operations and bytes of the vertical distribution's partial tiles.

Kept beside ``bench/work.py`` and in its conventions: real rows only, real
nonzeros only, compulsory bytes. On each chip the partial tile of a query
block scores every row of the block against every corpus row over the
dimensions dealt to that chip: each query row meets each of the chip's
nonzeros once, one multiply and one add, so a join costs a chip ``2 · n ·
nnz_chip`` operations. The shards partition the corpus's nonzeros, and the
counts are of one chip, the mean over the chips, so that a share of one
chip's peak cannot pass 100 %.
"""

from __future__ import annotations

from bench import work


def partial_tiles(n: int, nnz: int, chips: int, k: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one chip for one join of ``n`` rows holding
    ``nnz`` nonzeros over ``chips`` shards: the operations above, and the
    chip's share of the nonzeros read once and one ``Matches`` row per
    corpus row written."""
    nnz_chip = nnz / chips
    return 2.0 * n * nnz_chip, nnz_chip * work.CSR_ENTRY + work.result_bytes(n, k)
