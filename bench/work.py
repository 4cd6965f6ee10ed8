"""Operations and bytes a kernel's work needs, counted from the cell's data.

Operations follow the tiles the worklist names: real rows only (no row
padding of a block or a batch), the support's real width or the real
``m`` (no lane padding), the live tiles the worklist names (no bucket
padding to a power of two). Bytes are the compulsory traffic, whatever
the tiling: every operand block that a live tile touches is read once,
in the form it is stored in (a CSR block as its nonzeros, never a
gathered slab), and results are written once, as the final ``Matches``
rows: values and ids of ``k`` slots and one count.
"""

from __future__ import annotations

import numpy as np

F32 = 4
CSR_ENTRY = 8  # int32 index + float32 value


def result_bytes(rows: int, k: int) -> int:
    return rows * (k * (F32 + 4) + 4)


def block_rows(n: int, block: int) -> np.ndarray:
    """Real rows in each block of ``block`` rows."""
    nb = -(-n // block)
    rows = np.full(nb, block, np.int64)
    rows[-1] = n - (nb - 1) * block
    return rows


def csr_blocks(indices, nnz, block: int) -> tuple[np.ndarray, np.ndarray]:
    """Per block: the number of distinct dimensions and of nonzeros."""
    indices = np.asarray(indices)
    nnz = np.asarray(nnz)
    n, cap = indices.shape
    valid = np.arange(cap)[None, :] < nnz[:, None]
    support, nonzeros = [], []
    for lo in range(0, n, block):
        sl = slice(lo, min(lo + block, n))
        support.append(np.unique(indices[sl][valid[sl]]).size)
        nonzeros.append(int(nnz[sl].sum()))
    return np.asarray(support, np.int64), np.asarray(nonzeros, np.int64)


def sparse_selfjoin(
    worklist, rows: np.ndarray, support: np.ndarray, nonzeros: np.ndarray, k: int
) -> tuple[float, float]:
    """``(flops, bytes)`` of the CSR tile kernel over a self-join worklist.

    Tile ``(I, J)`` contracts block ``I`` densified on its own support with
    block ``J`` gathered onto that support: ``2 · rows[I] · rows[J] ·
    support[I]`` operations. Bytes: the nonzeros of every block a tile
    touches, once, and one ``Matches`` row per corpus row.
    """
    wl = np.asarray(worklist, np.int64).reshape(2, -1)
    i, j = wl
    flops = 2.0 * float(np.sum(rows[i] * rows[j] * support[i]))
    read = int(nonzeros[np.unique(wl)].sum()) * CSR_ENTRY
    return flops, float(read + result_bytes(int(rows.sum()), k))


def rect_dense(
    worklist, q_rows: np.ndarray, c_rows: np.ndarray, m: int, k: int
) -> tuple[float, float]:
    """``(flops, bytes)`` of the rectangular kernel over a query worklist.

    Tile ``(Q, C)`` scores ``q_rows[Q]`` queries against ``c_rows[C]``
    corpus rows of width ``m``. Bytes: every query block and corpus block a
    tile touches, once, and one ``Matches`` row per query.
    """
    wl = np.asarray(worklist, np.int64).reshape(2, -1)
    qi, ci = wl
    flops = 2.0 * float(np.sum(q_rows[qi] * c_rows[ci])) * m
    read = (q_rows[np.unique(qi)].sum() + c_rows[np.unique(ci)].sum()) * m * F32
    return flops, float(read + result_bytes(int(q_rows.sum()), k))
