"""Catalog rows made on the device from the seed, then copied to the host.

Clustered Gaussians: ``n_clusters`` centres drawn N(0, spread²) in every
dim, each row a centre plus N(0, noise²) noise, float32. The rows are made
in chunks of one shape (one compiled program) so that the device never
holds more than a chunk's rows besides the host-bound copy; the engine is
then built from the host copy, which it also needs for the labelled rows.
"""
from __future__ import annotations

import functools

import numpy as np

CHUNK_ROWS = 262_144


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.lru_cache(maxsize=None)
def _chunk_fn(rows: int, dim: int, n_clusters: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, centers, spread_noise):
        ka, kn = jax.random.split(key)
        assign = jax.random.randint(ka, (rows,), 0, n_clusters, jnp.int32)
        noise = jax.random.normal(kn, (rows, dim), jnp.float32)
        return centers[assign] + spread_noise * noise, assign

    return make


def make_catalog(seed: int, rows: int, dim: int, n_clusters: int,
                 spread: float, noise: float):
    """Returns (x [rows, dim] float32, cluster [rows] int32), on the host."""
    import jax
    import jax.numpy as jnp

    key = _key(seed)
    kc, kr = jax.random.split(key)
    centers = jnp.float32(spread) * jax.random.normal(
        kc, (n_clusters, dim), jnp.float32)
    x = np.empty((rows, dim), np.float32)
    cluster = np.empty(rows, np.int32)
    step = min(CHUNK_ROWS, rows)
    make = _chunk_fn(step, dim, n_clusters)
    for i, start in enumerate(range(0, rows, step)):
        xc, ac = make(jax.random.fold_in(kr, i), centers,
                      jnp.float32(noise))
        stop = min(start + step, rows)
        x[start:stop] = np.asarray(xc)[:stop - start]
        cluster[start:stop] = np.asarray(ac)[:stop - start]
        del xc, ac
    return x, cluster
