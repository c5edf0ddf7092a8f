"""Ahead-of-time compiles of the search kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler builds each program for a
``v5e:2x2`` topology that is described, not attached, and refuses what
the chip would refuse (a kernel that overflows VMEM, a slice the tiling
cannot express). Interpret-mode tests cannot see those failures. Every
test also checks that the Pallas kernel is really in the compiled
program (``tpu_custom_call``), not the jnp oracle.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and test collection runs in
every worker.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.box_scan import box_scan_pallas, box_scan_seg_pallas
from repro.kernels.l2dist import l2dist_pallas
from repro.kernels.zone_prune import zone_prune_pallas

BLOCK = 1024          # engine default rows per zone-map block
SUBSET_DIM = 6        # engine default dims per feature subset
LANES = 128           # ops.py pads the feature axis to this


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n_boxes", [64, 256])
def test_box_scan_compiles_for_v5e(one_chip, n_boxes):
    s = lambda *shape: _spec(one_chip, *shape)
    text = _compiled_text(
        lambda x, lo, hi: box_scan_pallas(x, lo, hi, tile_n=BLOCK,
                                          interpret=False),
        s(8 * BLOCK, LANES), s(n_boxes, LANES), s(n_boxes, LANES))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_boxes", [64, 256])
def test_box_scan_seg_compiles_for_v5e(one_chip, n_boxes):
    s = lambda *shape: _spec(one_chip, *shape)
    text = _compiled_text(
        lambda x, lo, hi, oh: box_scan_seg_pallas(x, lo, hi, oh,
                                                  tile_n=BLOCK,
                                                  interpret=False),
        s(8 * BLOCK, LANES), s(n_boxes, LANES), s(n_boxes, LANES),
        s(n_boxes, LANES))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_boxes", [64, 256])
def test_zone_prune_compiles_for_v5e(one_chip, n_boxes):
    s = lambda *shape: _spec(one_chip, *shape)
    text = _compiled_text(
        lambda zlo, zhi, blo, bhi: zone_prune_pallas(zlo, zhi, blo, bhi,
                                                     tile_z=512,
                                                     interpret=False),
        s(2048, LANES), s(2048, LANES), s(n_boxes, LANES),
        s(n_boxes, LANES))
    assert "tpu_custom_call" in text


def test_l2dist_compiles_for_v5e(one_chip):
    s = lambda *shape: _spec(one_chip, *shape)
    text = _compiled_text(
        lambda x, q: l2dist_pallas(x, q, tile_n=BLOCK, interpret=False),
        s(8 * BLOCK, LANES), s(8, LANES))
    assert "tpu_custom_call" in text


def test_fused_query_compiles_for_v5e(one_chip):
    """The engine's whole prune -> gather -> refine program at the
    default geometry: 2048 blocks of 1024 rows (a 2M-row subset), 64
    boxes owned by an 8-query window, capacity 256 blocks."""
    s = lambda *shape: _spec(one_chip, *shape)
    nb, b, q = 2048, 64, 8
    text = _compiled_text(
        lambda rows3, zlo, zhi, blo, bhi, oh: ops.fused_query(
            rows3, zlo, zhi, blo, bhi, oh, capacity=256, interpret=False),
        s(nb, BLOCK, SUBSET_DIM), s(nb, SUBSET_DIM), s(nb, SUBSET_DIM),
        s(b, SUBSET_DIM), s(b, SUBSET_DIM), s(b, q))
    # both kernels of the program: zone_prune and box_scan_seg
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("n_rows,capacity", [(1_000_000, 256),
                                             (590_326, 128)])
def test_accumulate_scores_compiles_for_v5e(one_chip, n_rows, capacity):
    """The dense score accumulate at the benchmark catalogs' sizes: a
    survivor-sized scatter-add by row id into the [N, 1] buffer, and no
    gather over the N rows in the compiled program."""
    nb = -(-n_rows // BLOCK)
    text = _compiled_text(
        ops.accumulate_scores,
        _spec(one_chip, n_rows, 1, dtype=jnp.int32),
        _spec(one_chip, capacity, BLOCK, 1, dtype=jnp.int32),
        _spec(one_chip, capacity, dtype=jnp.int32),
        _spec(one_chip, dtype=jnp.int32),
        _spec(one_chip, nb, BLOCK, dtype=jnp.int32))
    lines = text.splitlines()
    assert any(" scatter(" in l for l in lines)
    # the only gather is the grid's C rows: none yields a catalog's rows
    gathers = [l.split(" gather(")[0] for l in lines if " gather(" in l]
    assert gathers and not any(str(n_rows) in g for g in gathers), gathers
