"""Pallas stream-compaction kernel — "return only the filtered data".

TPU adaptation: compaction is a data-dependent permutation, which the VPU
cannot scatter directly.  Instead each event tile builds a one-hot
permutation matrix from the exclusive prefix-sum of the survivor mask and
*matmuls* the payload through it — turning an irregular gather into an MXU
operation (DESIGN.md §7).  Tiles are then stitched by a small jnp scan
using the per-tile counts.

Two-pass structure:
  pass 1 (in-kernel): tile-local compaction + survivor count per tile,
  pass 2 (jnp):       place each tile's packed rows at the global offset.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.skim_fused import compact_tile, stitch_tiles

EVENT_TILE = 512  # rows per tile; one-hot matmul is (512, 512) x (512, D)


def _compact_kernel(payload_ref, mask_ref, out_ref, count_ref):
    packed, count = compact_tile(mask_ref[0] > 0, payload_ref[...])
    out_ref[...] = packed.astype(out_ref.dtype)
    count_ref[pl.program_id(0)] = count


@functools.partial(jax.jit, static_argnames=("interpret", "event_tile"))
def stream_compact(
    payload: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    interpret: bool = True,
    event_tile: int = EVENT_TILE,
):
    """Pack surviving rows of ``payload`` ((E, D), any float/int dtype) to the
    front; zero-fill the tail.  Returns (packed (E, D), count ()).
    """
    E, D = payload.shape
    assert E % event_tile == 0, (E, event_tile)
    n_tiles = E // event_tile

    packed_tiles, counts = pl.pallas_call(
        _compact_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((event_tile, D), lambda i: (i, 0)),
            pl.BlockSpec((1, event_tile), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((event_tile, D), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E, D), payload.dtype),
            jax.ShapeDtypeStruct((n_tiles,), jnp.int32),
        ],
        interpret=interpret,
    )(payload, mask.astype(jnp.int32).reshape(1, E))

    # pass 2: stitch tiles at global offsets (jnp scan)
    return stitch_tiles(packed_tiles, counts), counts.sum()
