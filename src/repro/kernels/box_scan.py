"""box_scan Pallas kernel — the paper's inference hot spot.

Counts, for every database row, how many of the query boxes contain it
(a row's count is the DBranch ensemble "confidence"; count > 0 is the
binary prediction). This is the dense *refine* stage that runs over the
blocks surviving zone-map pruning.

TPU mapping: rows are tiled [TN, D] into VMEM; the (small) box set is
resident in VMEM across the whole grid; the containment test is pure VPU
work — (lo < x) & (x <= hi) reduced over D (8x128 lanes). D is padded to
a lane multiple by ops.py with (-inf, +inf) bounds so padding never
changes containment.

The box axis is walked INSIDE the kernel, BOX_CHUNK boxes per loop step:
each step materialises only a [TN, BOX_CHUNK, D] compare, so the kernel
fits VMEM at any box count and its compile time does not grow with the
box count (a whole-set [TN, B, D] compare overflows VMEM at B=64, and
its compile time grows with B).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BOX_CHUNK = 8     # boxes per in-kernel loop step (one sublane group)


def pad_box_chunks(lo: jax.Array, hi: jax.Array, *extra: jax.Array):
    """Pad the box axis to a BOX_CHUNK multiple with impossible boxes
    (lo=+inf, hi=-inf contain no row and overlap no zone); ``extra``
    per-box arrays (ownership maps) are padded with zeros."""
    pad = (-lo.shape[0]) % BOX_CHUNK
    if pad == 0:
        return (lo, hi) + extra
    pad_rows = lambda a, v: jnp.pad(a, ((0, pad), (0, 0)), constant_values=v)
    return ((pad_rows(lo, jnp.inf), pad_rows(hi, -jnp.inf))
            + tuple(pad_rows(e, 0) for e in extra))


def chunk_loop(n_boxes: int, body, init):
    """fori_loop over the box axis in BOX_CHUNK steps; ``body(start,
    acc)`` reads its boxes with ``ref[pl.ds(start, BOX_CHUNK), :]``."""
    def step(j, acc):
        return body(pl.multiple_of(j * BOX_CHUNK, BOX_CHUNK), acc)
    return jax.lax.fori_loop(0, n_boxes // BOX_CHUNK, step, init)


def _member(x, lo_ref, hi_ref, s):
    """[TN, BOX_CHUNK] containment of rows x in boxes s..s+BOX_CHUNK;
    half-open (lo, hi]."""
    lo = lo_ref[pl.ds(s, BOX_CHUNK), :]
    hi = hi_ref[pl.ds(s, BOX_CHUNK), :]
    inside = (x[:, None, :] > lo[None]) & (x[:, None, :] <= hi[None])
    return jnp.all(inside, axis=-1)


def _box_scan_kernel(x_ref, lo_ref, hi_ref, out_ref):
    """x: [TN, D]; lo/hi: [B, D]; out: [TN] int32 counts."""
    x = x_ref[...]

    def body(s, acc):
        return acc + _member(x, lo_ref, hi_ref, s).astype(jnp.int32).sum(-1)
    out_ref[...] = chunk_loop(lo_ref.shape[0], body,
                              jnp.zeros((x.shape[0],), jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def box_scan_pallas(x: jax.Array, lo: jax.Array, hi: jax.Array,
                    *, tile_n: int = 1024, interpret: bool = True) -> jax.Array:
    """x: [N, D] f32 (N % tile_n == 0, D % 128 == 0 — see ops.py),
    lo/hi: [B, D]. Returns [N] int32 box-membership counts."""
    n, d = x.shape
    lo, hi = pad_box_chunks(lo, hi)
    b = lo.shape[0]
    return pl.pallas_call(
        _box_scan_kernel,
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),   # row tile -> VMEM
            pl.BlockSpec((b, d), lambda i: (0, 0)),        # boxes resident
            pl.BlockSpec((b, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(x, lo, hi)


def _box_scan_seg_kernel(x_ref, lo_ref, hi_ref, oh_ref, out_ref):
    """Segmented variant for batched multi-query refine.

    x: [TN, D]; lo/hi: [B, D]; oh: [B, Q] box->segment one-hot;
    out: [TN, Q] int32 per-segment counts. Each chunk's [TN, BOX_CHUNK]
    membership mask is reduced per segment by a 0/1 matmul (MXU) instead
    of a plain sum — exact in f32 for any realistic box count (< 2^24
    boxes/segment)."""
    x = x_ref[...]

    def body(s, acc):
        member = _member(x, lo_ref, hi_ref, s).astype(jnp.float32)
        oh = oh_ref[pl.ds(s, BOX_CHUNK), :]
        return acc + jnp.dot(member, oh, preferred_element_type=jnp.float32)
    acc = chunk_loop(lo_ref.shape[0], body,
                     jnp.zeros(out_ref.shape, jnp.float32))
    out_ref[...] = acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def box_scan_seg_pallas(x: jax.Array, lo: jax.Array, hi: jax.Array,
                        onehot: jax.Array, *, tile_n: int = 1024,
                        interpret: bool = True) -> jax.Array:
    """x: [N, D] f32 (N % tile_n == 0, D % 128 == 0); lo/hi: [B, D];
    onehot: [B, Q] f32 (Q % 128 == 0 — see ops.py). Returns [N, Q] int32
    per-segment membership counts."""
    n, d = x.shape
    lo, hi, onehot = pad_box_chunks(lo, hi, onehot)
    b = lo.shape[0]
    q = onehot.shape[1]
    return pl.pallas_call(
        _box_scan_seg_kernel,
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),   # row tile -> VMEM
            pl.BlockSpec((b, d), lambda i: (0, 0)),        # boxes resident
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((b, q), lambda i: (0, 0)),        # ownership map
        ],
        out_specs=pl.BlockSpec((tile_n, q), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, q), jnp.int32),
        interpret=interpret,
    )(x, lo, hi, onehot)
