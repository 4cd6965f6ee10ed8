"""Seeded inputs of the cells, made by the benchmark itself.

The generators are the benchmark's own copies of ``repro.data``'s, so a
later change to the program cannot move the yardstick:

- :func:`sparse_zipf_csr` is ``repro.data.sparse.sparse_zipfian_corpus``
  vectorised, with the row lengths drawn so that the total number of
  nonzeros is exactly the configuration's. Each row draws its dimensions
  from a Zipf(``alpha``) popularity without replacement: the first distinct
  values of i.i.d. draws from the popularity, which is the same law as
  ``rng.choice(m, k, replace=False, p=pop)``.
- :func:`gaussian_rows` draws i.i.d. standard normal rows on the device, in
  one jitted call, in float32 (the type the program serves).

Every stream is derived from ``(seed, stream id)`` by ``SeedSequence``, so
any non-negative seed up to 64 bits gives its own data and the same seed
gives the same data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Stream ids: one per independent draw of a run.
STREAM_CORPUS = 1
STREAM_QUERIES = 2
STREAM_ORDER = 3
STREAM_SAMPLE = 4
STREAM_LENGTHS = 5


def seed_sequence(seed: int, stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % 2**64, int(stream)])


def numpy_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_sequence(seed, stream))


def jax_key(seed: int, stream: int):
    """A threefry key holding 64 bits of ``(seed, stream)``."""
    words = seed_sequence(seed, stream).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def zipf_popularity(m: int, alpha: float) -> np.ndarray:
    """Probability of dimension ``d`` proportional to ``(d + 1) ** -alpha``."""
    pop = np.arange(1, m + 1, dtype=np.float64) ** (-alpha)
    return pop / pop.sum()


def sparse_zipf_csr(
    n: int, m: int, nnz_total: int, alpha: float, seed: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Padded CSR ``(indices, values, nnz)`` of a Zipf corpus, rows unit-norm.

    ``nnz`` sums to exactly ``nnz_total`` (every row holds at least one
    entry); indices are sorted and unique per row, padding slots hold
    ``(0, 0.0)``; values are ``|N(0, 1)| + 0.05`` before normalization.
    The row lengths are one multinomial draw shared by every seed, dealt to
    the rows in the seed's own order, so the padded width (the longest
    row) and the work do not change from seed to seed.
    """
    if not n <= nnz_total <= n * m:
        raise ValueError(f"nnz_total={nnz_total} does not fit {n} rows of {m}")
    lengths = numpy_rng(0, STREAM_LENGTHS)
    rng = numpy_rng(seed, STREAM_CORPUS)
    nnz = rng.permutation(1 + lengths.multinomial(nnz_total - n, np.full(n, 1.0 / n)))
    if nnz.max() > m:
        raise ValueError("a row would need more distinct dimensions than m")
    cap = int(nnz.max())
    cdf = np.cumsum(zipf_popularity(m, alpha))
    cdf /= cdf[-1]
    dims = np.full((n, cap), m, np.int64)  # m sorts after every real dim
    todo = np.arange(n)
    draws_per_row = 4 * cap
    while todo.size:
        draws = np.searchsorted(
            cdf, rng.random((todo.size, draws_per_row)), side="right"
        )
        np.minimum(draws, m - 1, out=draws)
        order = np.argsort(draws, axis=1, kind="stable")
        ordered = np.take_along_axis(draws, order, axis=1)
        first_ordered = np.ones_like(ordered, bool)
        first_ordered[:, 1:] = ordered[:, 1:] != ordered[:, :-1]
        first = np.empty_like(first_ordered)
        np.put_along_axis(first, order, first_ordered, axis=1)
        rank = np.cumsum(first, axis=1)
        need = nnz[todo][:, None]
        keep = first & (rank <= need)
        done = keep.sum(axis=1) == nnz[todo]
        r, c = np.nonzero(keep & done[:, None])
        dims[todo[r], rank[r, c] - 1] = draws[r, c]
        todo = todo[~done]
        draws_per_row *= 2
    dims.sort(axis=1)
    valid = np.arange(cap)[None, :] < nnz[:, None]
    values = np.where(valid, np.abs(rng.standard_normal((n, cap))) + 0.05, 0.0)
    values /= np.sqrt((values**2).sum(axis=1, keepdims=True))
    indices = np.where(valid, dims, 0).astype(np.int32)
    return indices, values.astype(np.float32), nnz.astype(np.int32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, n: int, m: int):
    return jax.random.normal(key, (n, m), jnp.float32)


def gaussian_rows(seed: int, stream: int, n: int, m: int):
    """``(n, m)`` float32 standard normal rows on the default device."""
    return _normal(jax_key(seed, stream), n, m)


def normalize_f32(x):
    """Unit rows in float32 on the device (zero rows stay zero)."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(norm, jnp.float32(1e-30))


def normalize_f64(x: np.ndarray) -> np.ndarray:
    """Unit rows in float64 on the host: the reference's inputs."""
    x = np.asarray(x, np.float64)
    norm = np.sqrt((x * x).sum(axis=-1, keepdims=True))
    return x / np.maximum(norm, 1e-300)


def poisson_gaps(n: int, rate: float, seed: int) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process of ``rate`` per second.

    Every seed gets the same set of gaps (the exponential distribution's
    ``n`` mid-quantiles) in its own order, so the offered load over a window
    does not vary from seed to seed while the arrivals stay Poisson-like.
    """
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return numpy_rng(seed, STREAM_ORDER).permutation(gaps)
