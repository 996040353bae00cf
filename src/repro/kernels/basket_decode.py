"""Pallas basket-decode kernel — the DPU decompression-engine analogue.

Decodes the ``bitpack`` codec (repro.data.codecs): per basket, ``B``
bit-planes of ``W`` uint32 words reconstruct up to ``W*32`` codes, followed
by the inverse transform:

  kind 0 (int)   : zigzag^-1 then inclusive prefix *sum*,
  kind 1 (float) : inclusive prefix *xor* then bitcast to f32,
  kind 2 (bool)  : identity.

Bit extraction is broadcast/shift vector arithmetic — no gathers, no
byte shuffles.  The kernel keeps a basket's values as a (W, 32) tile
(word down the sublanes, bit on the lanes), so no lane reshape is needed,
and takes its scans as exact 0/1 matmuls on the MXU (:func:`_prefix_count`).
The jnp mirror (:func:`basket_decode_ref`) uses a log-step Hillis–Steele
scan instead.  Grid = one basket per step; a basket's planes ((B, W)
uint32, typically <= 32x128 words = 16 KiB) sit comfortably in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KIND_INT, KIND_FLOAT, KIND_BOOL = 0, 1, 2

_EXACT = jax.lax.Precision.HIGHEST


def _log_scan(x: jnp.ndarray, combine) -> jnp.ndarray:
    """Hillis–Steele inclusive scan over the last axis (static log steps)."""
    n = x.shape[-1]
    shift = 1
    while shift < n:
        pad = [(0, 0)] * (x.ndim - 1)
        shifted = jnp.pad(x[..., :-shift], [*pad, (shift, 0)])
        x = combine(x, shifted)
        shift *= 2
    return x


def _prefix_count(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a (W, 32) f32 tile of small non-negative
    integers in row-major value order (value ``w*32 + s``), as matmuls:
    the in-row scan by an upper-triangular (32, 32) 0/1 matrix, the
    carry-in of earlier rows by a strictly-lower (W, W) one applied to
    the row sums.  Exact while every partial sum stays below 2**24."""
    W, S = x.shape
    si = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    sj = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    upper = (si <= sj).astype(jnp.float32)
    wi = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
    wj = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    earlier = (wj < wi).astype(jnp.float32)

    def mm(a, b):
        return jnp.dot(a, b, precision=_EXACT, preferred_element_type=jnp.float32)

    in_row = mm(x, upper)
    row_sums = mm(x, jnp.ones((S, S), jnp.float32))  # each lane holds its row's sum
    return in_row + mm(earlier, row_sums)


def _prefix_sum_i32(x: jnp.ndarray) -> jnp.ndarray:
    """Wrap-exact int32 inclusive prefix sum (row-major over (W, 32)):
    the sum mod 2**32 of the four byte lanes' exact prefix counts, each
    shifted back into place."""
    total = jnp.zeros(x.shape, jnp.int32)
    for k in range(4):
        byte = ((x >> (8 * k)) & 0xFF).astype(jnp.float32)
        total = total + (_prefix_count(byte).astype(jnp.int32) << (8 * k))
    return total


def _prefix_xor_i32(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix xor (row-major over (W, 32)): bit ``b`` of the
    result is the parity of the prefix count of bit ``b``."""
    def body(b, acc):
        bit = ((x >> b) & 1).astype(jnp.float32)
        parity = _prefix_count(bit).astype(jnp.int32) & 1
        return acc | (parity << b)

    return jax.lax.fori_loop(0, 32, body, jnp.zeros(x.shape, jnp.int32))


def _decode_kernel(planes_ref, firsts_ref, out_ref, *, kind: int, n_bits: int):
    """One basket.  ``planes_ref`` block (1, W, B) int32 (bit-planes on
    the lane axis, plane words down the sublanes), ``firsts_ref`` the
    whole (N,) int32 first-value vector in SMEM, ``out_ref`` block
    (1, W, 32): value ``w*32 + s`` is bit ``s`` of word ``w`` of every
    plane, so the output reshapes to (N, W*32) for free."""
    planes = planes_ref[0]  # (W, B)
    W = planes.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (W, 32), 1)
    codes = jnp.zeros((W, 32), jnp.int32)
    for j in range(n_bits):
        codes = codes | (((planes[:, j : j + 1] >> lane) & 1) << j)

    if kind == KIND_BOOL:
        out_ref[0] = codes
        return
    first_slot = (jax.lax.broadcasted_iota(jnp.int32, (W, 32), 0) == 0) & (lane == 0)
    first = firsts_ref[pl.program_id(0)]
    if kind == KIND_INT:
        # zigzag^-1: logical >> 1 (the arithmetic shift's sign bits masked)
        dec = ((codes >> 1) & 0x7FFFFFFF) ^ -(codes & 1)
        out_ref[0] = _prefix_sum_i32(jnp.where(first_slot, first, dec))
        return
    # KIND_FLOAT: prefix-xor of the bit patterns
    out_ref[0] = _prefix_xor_i32(jnp.where(first_slot, first, codes))


@functools.partial(jax.jit, static_argnames=("kind", "n_bits", "out_dtype"))
def basket_decode_ref(
    planes: jnp.ndarray,
    firsts: jnp.ndarray,
    *,
    kind: int,
    n_bits: int,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """Jitted jnp mirror of the Pallas decode kernel (the XLA device tier).

    Same bit-extract + inverse-transform body as :func:`_decode_kernel`,
    vectorized over the basket axis — this is what backs the device
    decode path on hosts without a TPU (``repro.kernels.ops
    .basket_decode_batch``), and it is bit-identical to the host codec:
    the int path is a wrap-exact int32 prefix sum, the float path an
    exact prefix xor, bools an identity.
    """
    N, B, W = planes.shape
    V = W * 32
    shifts = jnp.arange(32, dtype=jnp.uint32)
    codes = jnp.zeros((N, V), dtype=jnp.uint32)
    for j in range(n_bits):
        bits = (planes[:, j, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
        codes = codes | (bits.reshape(N, V) << jnp.uint32(j))

    if kind == KIND_BOOL:
        return codes.astype(out_dtype)
    pos = jax.lax.broadcasted_iota(jnp.int32, (N, V), 1)
    if kind == KIND_INT:
        dec = (codes >> 1).astype(jnp.int32) ^ -(codes & 1).astype(jnp.int32)
        first = jax.lax.bitcast_convert_type(
            firsts.astype(jnp.uint32), jnp.int32
        )
        dec = jnp.where(pos == 0, first[:, None], dec)
        return _log_scan(dec, jnp.add).astype(out_dtype)
    # KIND_FLOAT: prefix-xor then bitcast
    codes = jnp.where(pos == 0, firsts.astype(jnp.uint32)[:, None], codes)
    acc = _log_scan(codes, jnp.bitwise_xor)
    return jax.lax.bitcast_convert_type(acc, jnp.float32).astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("kind", "n_bits", "out_dtype", "interpret")
)
def basket_decode(
    planes: jnp.ndarray,
    firsts: jnp.ndarray,
    *,
    kind: int,
    n_bits: int,
    out_dtype=jnp.float32,
    interpret: bool = True,
) -> jnp.ndarray:
    """Decode ``N`` same-shaped baskets.

    Args:
      planes: (N, B, W) uint32 bit-planes (planes >= the true bit width are
              zero-padded by the encoder batcher).
      firsts: (N,) uint32 first-value bit patterns.
      kind, n_bits: static codec parameters for the batch.
    Returns: (N, W*32) decoded values of ``out_dtype``.

    The kernel works on int32 bit patterns with the planes transposed to
    (N, W, B); the casts and the transpose are XLA ops around it.
    """
    N, B, W = planes.shape
    assert n_bits <= B
    assert W * 32 * 0xFF < 1 << 24, "prefix counts must stay f32-exact"
    as_i32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    planes_t = as_i32(jnp.swapaxes(planes.astype(jnp.uint32), 1, 2))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, kind=kind, n_bits=n_bits),
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, W, B), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, W, 32), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, W, 32), jnp.int32),
        interpret=interpret,
    )(planes_t, as_i32(firsts.astype(jnp.uint32)))
    out = out.reshape(N, W * 32)
    if kind == KIND_FLOAT:
        out = jax.lax.bitcast_convert_type(out, jnp.float32)
    return out.astype(out_dtype)
