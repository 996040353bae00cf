"""Fused predicate-eval + stream-compact kernel (beyond-paper; DESIGN.md §7).

The paper evaluates the predicate, then gathers survivors — two passes
over the event data.  On TPU both fit in one VMEM round trip: each event
tile evaluates the compiled program AND compacts its surviving payload
rows via the one-hot MXU permutation in the same kernel body, so the mask
never travels to HBM.  One pass, one output stream — exactly the "return
only the filtered data" contract, minus a full HBM round trip of the
payload + mask.

Data-layout contract (the engine's ``near_data`` fast path rides on it):
inputs are the padded window tensors from
``repro.core.neardata.build_padded_inputs`` — terms (T, E, K), validity /
HT weights (G, E, K), payload (E, D) — and by convention payload column 0
is the *local event index*, so the compacted output alone lets the host
recover the survivor mask without the mask ever leaving the device.
Tiles are stitched to a globally front-packed stream by
:func:`stitch_tiles`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.predicate_eval import COMPILER_PARAMS, Program, fit_event_tile
from repro.kernels.ref import predicate_mask

EVENT_TILE = 512

_EXACT = jax.lax.Precision.HIGHEST


def compact_tile(mask, payload):
    """Front-pack the surviving rows of one event tile.

    ``mask`` (Eb,) bool, ``payload`` (Eb, D).  The destination of each
    survivor is the exclusive prefix count of the mask, taken as a
    (1, Eb) x (Eb, Eb) matmul with a strictly-upper 0/1 matrix (exact:
    0/1 operands, counts <= Eb); the rows then move through a one-hot
    permutation matmul on the MXU.  Both matmuls run at HIGHEST precision,
    so f32 payload values (column 0 carries the event index) pass through
    bit-exact.  Returns (packed (Eb, D) f32, survivor count int32).
    """
    Eb = payload.shape[0]
    m_row = mask.astype(jnp.float32).reshape(1, Eb)
    src = jax.lax.broadcasted_iota(jnp.int32, (Eb, Eb), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (Eb, Eb), 1)
    before = (src < dst).astype(jnp.float32)
    pos = jnp.dot(m_row, before, precision=_EXACT,
                  preferred_element_type=jnp.float32)  # (1, Eb)
    # one-hot permutation: P[j, i] = 1 iff row i survives and lands at j
    onehot = (src.astype(jnp.float32) == pos) & (m_row > 0)
    packed = jnp.dot(
        onehot.astype(jnp.float32),
        payload.astype(jnp.float32),
        precision=_EXACT,
        preferred_element_type=jnp.float32,
    )
    return packed, mask.astype(jnp.int32).sum()


def _fused_kernel(terms_ref, valid_ref, weights_ref, payload_ref,
                  out_ref, count_ref, *, program: Program):
    mask = predicate_mask(
        program, terms_ref[...], valid_ref[...], weights_ref[...]
    )
    packed, count = compact_tile(mask, payload_ref[...])
    out_ref[...] = packed.astype(out_ref.dtype)
    count_ref[pl.program_id(0)] = count


@jax.jit
def stitch_tiles(packed_tiles, counts):
    """Place each tile's front-packed rows at its global offset.

    ``counts`` holds one survivor count per tile, which fixes the tile
    size.  Rows beyond a tile's survivor count are zero and tiles write to
    disjoint [off, off+count) ranges, so accumulate-add is exact.  Shared
    epilogue of the fused and two-pass compaction paths.
    """
    E, D = packed_tiles.shape
    n_tiles = counts.shape[0]
    event_tile = E // n_tiles
    tiles = packed_tiles.reshape(n_tiles, event_tile, D)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])

    def place(acc, inp):
        tile, off = inp
        cur = jax.lax.dynamic_slice(acc, (off, 0), (event_tile, D))
        return jax.lax.dynamic_update_slice(acc, cur + tile, (off, 0)), None

    out0 = jnp.zeros((E + event_tile, D), packed_tiles.dtype)
    out, _ = jax.lax.scan(place, out0, (tiles, offsets))
    return out[:E]


def _fused_kernel_batched(terms_ref, valid_ref, weights_ref, payload_ref,
                          out_ref, count_ref, *, program: Program):
    """Window-batched body: blocks carry a leading window dim of 1 (the
    outer grid axis); the evaluation is the same shared predicate +
    one-hot compaction as :func:`_fused_kernel`."""
    mask = predicate_mask(
        program, terms_ref[0], valid_ref[0], weights_ref[0]
    )
    packed, count = compact_tile(mask, payload_ref[0])
    out_ref[0] = packed.astype(out_ref.dtype)
    count_ref[pl.program_id(0), pl.program_id(1)] = count


@functools.partial(jax.jit, static_argnames=("program", "interpret", "event_tile"))
def skim_fused_batch(terms, valid, weights, payload, *, program: Program,
                     interpret: bool = True, event_tile: int = EVENT_TILE):
    """Window-batched one-pass skim: ONE dispatch for a whole batch of
    padded windows (DESIGN.md §16).

    Inputs carry a leading window axis — terms (B,T,E,K), valid/weights
    (B,G,E,K), payload (B,E,D) — and the grid runs (B, E/tile): the same
    fused kernel body as :func:`skim_fused`, with the batch as the outer
    (slowest) grid dimension so each window's tiles stay VMEM-local.
    ``event_tile`` is an upper bound: wide programs get a smaller tile
    (:func:`~repro.kernels.predicate_eval.fit_event_tile`).  Returns
    per-window per-tile packed payload (B,E,D) + per-tile counts
    (B, n_tiles); stitch per window with :func:`stitch_tiles`.
    """
    Bn, T, E, K = terms.shape
    G = valid.shape[1]
    D = payload.shape[2]
    event_tile = fit_event_tile(event_tile, T + 2 * G, K)
    assert E % event_tile == 0
    n_tiles = E // event_tile

    return pl.pallas_call(
        functools.partial(_fused_kernel_batched, program=program),
        grid=(Bn, n_tiles),
        in_specs=[
            pl.BlockSpec((1, T, event_tile, K), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, event_tile, K), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, event_tile, K), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, event_tile, D), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, event_tile, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bn, E, D), payload.dtype),
            jax.ShapeDtypeStruct((Bn, n_tiles), jnp.int32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(terms, valid, weights, payload)


@functools.partial(jax.jit, static_argnames=("program", "interpret", "event_tile"))
def skim_fused(terms, valid, weights, payload, *, program: Program,
               interpret: bool = True, event_tile: int = EVENT_TILE):
    """One-pass skim: (T,E,K),(G,E,K),(G,E,K),(E,D) -> per-tile packed
    payload (E, D) + per-tile survivor counts (n_tiles,).  ``event_tile``
    is an upper bound, as in :func:`skim_fused_batch`."""
    T, E, K = terms.shape
    G = valid.shape[0]
    D = payload.shape[1]
    event_tile = fit_event_tile(event_tile, T + 2 * G, K)
    assert E % event_tile == 0
    n_tiles = E // event_tile

    return pl.pallas_call(
        functools.partial(_fused_kernel, program=program),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((T, event_tile, K), lambda i: (0, i, 0)),
            pl.BlockSpec((G, event_tile, K), lambda i: (0, i, 0)),
            pl.BlockSpec((G, event_tile, K), lambda i: (0, i, 0)),
            pl.BlockSpec((event_tile, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((event_tile, D), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E, D), payload.dtype),
            jax.ShapeDtypeStruct((n_tiles,), jnp.int32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(terms, valid, weights, payload)
