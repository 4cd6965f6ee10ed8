"""The packet folds merge every row block's packets at once, exactly.

``ops.fold_rect_packets`` and ``ops.fold_packets`` must give what a
sequential merge of one packet at a time gives — values, ids and counts bit
for bit, ties included — because the early-exit path replays that merge and
is compared with the normal path value for value. The reference below is
that sequential merge, kept here as the definition.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.matches import NEG_INF
from repro.kernels.apss_block.fused import NEG_LARGE, _VALID
from repro.kernels.apss_block.ops import (
    fold_packets,
    fold_ranks,
    fold_rect_packets,
    pad_worklist,
)
from repro.obs import Tracer

BLOCK = 8


def _merge(cv, ci, cc, blk, pv, pi, pc, k):
    vals = jnp.concatenate([cv[blk], pv], axis=1)
    idxs = jnp.concatenate([ci[blk], pi], axis=1)
    tv, sel = jax.lax.top_k(vals, k)
    ti = jnp.take_along_axis(idxs, sel, axis=1)
    ti = jnp.where(tv > _VALID, ti, -1)
    return cv.at[blk].set(tv), ci.at[blk].set(ti), cc.at[blk].add(pc)


def _empty(grid, block, k):
    return (
        jnp.full((grid, block, k), -jnp.inf, jnp.float32),
        jnp.full((grid, block, k), -1, jnp.int32),
        jnp.zeros((grid, block), jnp.int32),
    )


def _flat(cv, ci, cc):
    grid, block, k = cv.shape
    return (
        np.asarray(jnp.where(ci >= 0, cv, NEG_INF).reshape(grid * block, k)),
        np.asarray(ci.reshape(grid * block, k)),
        np.asarray(cc.reshape(grid * block)),
    )


def reference_rect(ij, tvalid, fv, fi, fc, *, grid_q, block_q, k):
    """One packet at a time, in worklist order; invalid entries neutral."""
    fv = jnp.where(tvalid[:, None, None], fv, NEG_INF)
    fi = jnp.where(tvalid[:, None, None], fi, -1)
    fc = jnp.where(tvalid[:, None], fc, 0)

    def step(carry, inp):
        ib, v, i, c = inp
        return _merge(*carry, ib, v, i, c, k), None

    out, _ = jax.lax.scan(step, _empty(grid_q, block_q, k), (ij[0], fv, fi, fc))
    return _flat(*out)


def reference_selfjoin(ij, fv, fi, fc, bv, bi, bc, *, grid_m, block_m, k):
    """Entry t's forward packet into block ``ij[0, t]``, then its mirror
    packet into block ``ij[1, t]``."""

    def step(carry, inp):
        ib, jb, v, i, c, w, j, d = inp
        carry = _merge(*carry, ib, v, i, c, k)
        return _merge(*carry, jb, w, j, d, k), None

    out, _ = jax.lax.scan(
        step, _empty(grid_m, block_m, k), (ij[0], ij[1], fv, fi, fc, bv, bi, bc)
    )
    return _flat(*out)


def _packets(rng, n, cols, k, kind):
    """``n`` kernel-like packets ``(n, BLOCK, k)``: each row's k best of a
    tile, descending, ``NEG_LARGE`` / −1 where the tile has fewer. ``cols``
    ``(n,)`` is each packet's corpus block, so ids never repeat in a block.
    Values are quantised to eighths so that ties occur across packets."""
    v = np.round(rng.random((n, BLOCK, k)) * 8) / 8
    v = -np.sort(-v, axis=2).astype(np.float32)
    held = rng.integers(0, k + 1, (n, BLOCK, 1))
    empty = np.arange(k)[None, None, :] >= held
    ids = cols[:, None, None] * 4 * k + rng.permuted(
        np.broadcast_to(np.arange(4 * k), (n, BLOCK, 4 * k)), axis=2
    )[..., :k]
    if kind == "neg_large":
        empty[:] = True
    v = np.where(empty, NEG_LARGE, v).astype(np.float32)
    if kind == "neg_inf":
        v[:] = -np.inf
        empty[:] = True
    i = np.where(empty, -1, ids).astype(np.int32)
    held = held[..., 0]
    c = held + np.where(held == k, rng.integers(0, 3, (n, BLOCK)), 0)
    return v, i, c.astype(np.int32)


def _rect_worklist(rng, grid_q, grid_c, T):
    """``T`` distinct live tiles in random order over ``grid_q`` query blocks;
    the last query block (of more than one) receives none, the others
    uneven shares."""
    rows = max(1, grid_q - 1)
    w = rng.random(rows) ** 3 + 0.05
    cells = [(q, c) for q in range(rows) for c in range(grid_c)]
    p = np.array([w[q] for q, _ in cells])
    pick = rng.choice(len(cells), size=T, replace=False, p=p / p.sum())
    return np.array([cells[x] for x in pick], np.int32).T


def _assert_same(got, ref):
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), r)


RECT_CASES = [
    # grid_q, grid_c, live tiles, k, packet kind
    (1, 6, 5, 1, "ties"),
    (1, 6, 5, 10, "ties"),
    (3, 7, 13, 1, "ties"),
    (3, 7, 13, 10, "ties"),
    (5, 9, 29, 10, "ties"),
    (5, 9, 29, 1, "ties"),
    (3, 7, 13, 10, "neg_large"),
    (3, 7, 13, 10, "neg_inf"),
    (5, 9, 3, 10, "ties"),  # far fewer entries than blocks: several passes
    (5, 9, 3, 1, "ties"),
]


@pytest.mark.parametrize("grid_q,grid_c,live,k,kind", RECT_CASES)
def test_rect_fold_matches_the_sequential_merge(grid_q, grid_c, live, k, kind):
    rng = np.random.default_rng(100 * grid_q + 10 * live + k)
    wl = _rect_worklist(rng, grid_q, grid_c, live)
    ij, tvalid = pad_worklist(wl)
    # the padding entries carry tile (0, 0)'s coordinates and live packets:
    # only the validity mask keeps them off block 0
    assert ij.shape[1] > live or live & (live - 1) == 0
    fv, fi, fc = _packets(rng, ij.shape[1], ij[1], k, kind)
    args = tuple(map(jnp.asarray, (ij, tvalid, fv, fi, fc)))
    got = jax.jit(
        fold_rect_packets, static_argnames=("grid_q", "grid_c", "block_q", "k")
    )(*args, grid_q=grid_q, grid_c=grid_c, block_q=BLOCK, k=k)
    ref = reference_rect(*args, grid_q=grid_q, block_q=BLOCK, k=k)
    _assert_same(got, ref)
    if kind != "ties":
        assert (ref[1] == -1).all() and (ref[0] == -np.inf).all()
    elif grid_q > 1:
        # the block without entries is empty; the others hold values
        assert (ref[1][-BLOCK:] == -1).all() and (ref[2][-BLOCK:] == 0).all()
        assert (ref[1][:-BLOCK] >= 0).any()


def _upper_worklist(rng, grid_m, T):
    """``T`` distinct upper-triangular tiles in random order, diagonal ones
    among them, the last row block in none of them as a forward packet."""
    cells = [(i, j) for i in range(grid_m - 1) for j in range(i, grid_m)]
    pick = rng.choice(len(cells), size=T, replace=False)
    wl = np.array([cells[x] for x in pick], np.int32).T
    if not (wl[0] == wl[1]).any():
        wl[:, 0] = (wl[0, 0], wl[0, 0])
    return wl


SELF_CASES = [
    # grid_m, live tiles, k, packet kind
    (1, 1, 1, "ties"),
    (1, 1, 10, "ties"),
    (3, 5, 1, "ties"),
    (3, 5, 10, "ties"),
    (5, 11, 10, "ties"),
    (5, 11, 1, "ties"),
    (5, 11, 10, "neg_large"),
    (5, 11, 10, "neg_inf"),
    (6, 2, 10, "ties"),  # far fewer entries than blocks: several passes
]


@pytest.mark.parametrize("grid_m,live,k,kind", SELF_CASES)
def test_selfjoin_fold_matches_the_sequential_merge(grid_m, live, k, kind):
    rng = np.random.default_rng(1000 + 100 * grid_m + 10 * live + k)
    if grid_m == 1:
        wl = np.zeros((2, 1), np.int32)
    else:
        wl = _upper_worklist(rng, grid_m, live)
    fv, fi, fc = _packets(rng, live, wl[1], k, kind)
    bv, bi, bc = _packets(rng, live, wl[0] + grid_m, k, kind)
    diag = wl[0] == wl[1]
    assert diag.any()
    bv[diag], bi[diag], bc[diag] = NEG_LARGE, -1, 0  # the kernel's empty mirror
    # some block receives both forward and mirror packets
    assert set(wl[0]) & set(wl[1][~diag]) or grid_m == 1 or live < 3
    args = tuple(map(jnp.asarray, (wl, fv, fi, fc, bv, bi, bc)))
    got = jax.jit(fold_packets, static_argnames=("grid_m", "block_m", "k"))(
        *args, grid_m=grid_m, block_m=BLOCK, k=k
    )
    ref = reference_selfjoin(*args, grid_m=grid_m, block_m=BLOCK, k=k)
    _assert_same(got, ref)
    if kind == "ties":
        assert (ref[1] >= 0).any()


def _primitives(jaxpr):
    """Every primitive of a closed jaxpr, sub-jaxprs included, with its
    equation."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize(
    "grid,T,grid_c",
    # T entries, bucket-padded, over grid × grid_c tiles, as in a batch of
    # queries that keeps most tiles live (the batch cell: 4, 32,768, 4,624)
    [(4, 64, 16), (2, 512, 256), (3, 16, 4)],
)
def test_folds_hold_no_loop_over_the_worklist(grid, T, grid_c):
    k = 10
    pv = jnp.zeros((T, BLOCK, k))
    pi = jnp.zeros((T, BLOCK, k), jnp.int32)
    pc = jnp.zeros((T, BLOCK), jnp.int32)
    ij = jnp.zeros((2, T), jnp.int32)
    rect = jax.make_jaxpr(
        lambda *a: fold_rect_packets(
            *a, grid_q=grid, grid_c=grid_c, block_q=BLOCK, k=k
        )
    )(ij, jnp.ones((T,), bool), pv, pi, pc)
    selfjoin = jax.make_jaxpr(
        lambda *a: fold_packets(*a, grid_m=grid, block_m=BLOCK, k=k)
    )(ij, pv, pi, pc, pv, pi, pc)
    for jaxpr in (rect.jaxpr, selfjoin.jaxpr):
        names = {e.primitive.name for e in _primitives(jaxpr)}
        assert not names & {"scan", "while"}, names
        # one stable top-k over the slots
        assert sum(e.primitive.name == "top_k" for e in _primitives(jaxpr)) == 1


def test_a_sparse_worklist_folds_in_passes_the_size_of_its_packets():
    # 8 entries over 64 query blocks of 64 corpus blocks: ranks =
    # min(8, 64) = 8, but a pass lays out ⌈8 / 64⌉ = 1 rank a block, so
    # the slab stays one packet a block; its loop runs while a block has
    # packets left, never once per entry
    grid, T, k = 64, 8, 4
    jaxpr = jax.make_jaxpr(
        lambda *a: fold_rect_packets(*a, grid_q=grid, grid_c=64, block_q=BLOCK, k=k)
    )(
        jnp.zeros((2, T), jnp.int32), jnp.ones((T,), bool),
        jnp.zeros((T, BLOCK, k)), jnp.zeros((T, BLOCK, k), jnp.int32),
        jnp.zeros((T, BLOCK), jnp.int32),
    ).jaxpr
    loops = [e for e in _primitives(jaxpr) if e.primitive.name in ("scan", "while")]
    assert [e.primitive.name for e in loops] == ["while"]
    gathers = [
        e for e in _primitives(loops[0].params["body_jaxpr"].jaxpr)
        if e.primitive.name == "gather"
    ]
    assert any(e.outvars[0].aval.shape == (grid, 1, BLOCK, k) for e in gathers)


def test_fold_slots_annotate_both_worklist_spans():
    from repro.data.sparse import perturbed_queries, sparse_clustered_corpus
    from repro.kernels.apss_block import sparse
    from repro.serving import build_index, query

    sp = sparse_clustered_corpus(300, 256, 8.0, n_clusters=4, seed=0)
    index = build_index(sp, block_rows=64)
    Q = jnp.asarray(perturbed_queries(sp, 40, seed=1))
    with Tracer() as tr:
        jax.block_until_ready(sparse.apss_sparse_compacted(sp, 0.2, 8, block_m=128))
        jax.block_until_ready(query.query_topk(index, Q, 0.2, 8, block_q=16))
    spans = {s.name: s.attrs for s in tr.walk()}
    grid_m = -(-300 // 128)
    wl = spans["apss/worklist"]
    assert wl["live"] > 0
    assert wl["fold_slots"] == grid_m * fold_ranks(2 * wl["live"], grid_m + 1)
    q = spans["query/worklist"]
    grid_q, grid_c = -(-40 // 16), -(-index.n // 64)
    assert q["live"] > 0
    assert q["fold_slots"] == grid_q * fold_ranks(q["entries"], grid_c)
    assert q["fold_slots"] <= grid_q * grid_c
