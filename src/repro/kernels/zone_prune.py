"""zone_prune Pallas kernel — the index *prune* stage.

Tests every (block zone, query box) pair for interval overlap. A zone is
a per-block [min, max] bounding box; a block can only contain matches for
box q if the boxes overlap on EVERY dimension. The surviving-block mask
drives the gather feeding box_scan — together they are the TPU-native
replacement for the paper's k-d tree traversal (DESIGN.md §2).

VPU-only compares, walked over the box axis BOX_CHUNK boxes per loop
step like box_scan (VMEM use and compile time independent of the box
count). A chunk's [TZ, BOX_CHUNK] overlap lands in its output columns
through a 0/1 placement matmul, because a store at a lane offset that is
not a multiple of 128 does not compile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.box_scan import BOX_CHUNK, chunk_loop, pad_box_chunks


def _zone_prune_kernel(zlo_ref, zhi_ref, blo_ref, bhi_ref, out_ref):
    """zones: [TZ, D] lo/hi; boxes: [B, D] lo/hi; out: [TZ, B] bool."""
    zlo = zlo_ref[...]
    zhi = zhi_ref[...]
    b = out_ref.shape[1]

    def body(s, acc):
        blo = blo_ref[pl.ds(s, BOX_CHUNK), :]
        bhi = bhi_ref[pl.ds(s, BOX_CHUNK), :]
        # overlap on dim d: zone_hi > box_lo  AND  zone_lo <= box_hi
        # (half-open boxes (lo, hi]: a zone whose max == box_lo can't match)
        ov = jnp.all((zhi[:, None, :] > blo[None])
                     & (zlo[:, None, :] <= bhi[None]), axis=-1)
        shape = (BOX_CHUNK, b)
        place = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                 == jax.lax.broadcasted_iota(jnp.int32, shape, 0) + s)
        return acc + jnp.dot(ov.astype(jnp.float32),
                             place.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
    acc = chunk_loop(b, body, jnp.zeros(out_ref.shape, jnp.float32))
    out_ref[...] = acc > 0.5


@functools.partial(jax.jit, static_argnames=("tile_z", "interpret"))
def zone_prune_pallas(zlo: jax.Array, zhi: jax.Array,
                      blo: jax.Array, bhi: jax.Array,
                      *, tile_z: int = 512, interpret: bool = True) -> jax.Array:
    """zlo/zhi: [NZ, D]; blo/bhi: [B, D]. Returns [NZ, B] bool overlap."""
    nz, d = zlo.shape
    b_real = blo.shape[0]
    blo, bhi = pad_box_chunks(blo, bhi)
    b = blo.shape[0]
    out = pl.pallas_call(
        _zone_prune_kernel,
        grid=(nz // tile_z,),
        in_specs=[
            pl.BlockSpec((tile_z, d), lambda i: (i, 0)),
            pl.BlockSpec((tile_z, d), lambda i: (i, 0)),
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((b, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_z, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nz, b), jnp.bool_),
        interpret=interpret,
    )(zlo, zhi, blo, bhi)
    return out[:, :b_real]
