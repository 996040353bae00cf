"""Per-kernel shape/dtype sweeps against the ref.py oracles (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data.codecs import bitpack_encode, bitpack_raw_parts
from repro.kernels import ops, ref
from repro.kernels.predicate_eval import Group, Program
from repro.kernels.ref import GROUP_ANY, GROUP_COUNT, GROUP_HT, OP_IDS

RNG = np.random.default_rng(7)


def _program():
    return Program(
        groups=(
            Group(GROUP_COUNT, (0, 1), (OP_IDS[">"], OP_IDS["abs<"]), (20.0, 2.4)),
            Group(GROUP_HT, (2,), (OP_IDS[">"],), (30.0,),
                  cmp_op=OP_IDS[">"], cmp_thr=100.0),
            Group(GROUP_ANY, (3,), (OP_IDS[">="],), (0.5,)),
        ),
        term_branches=("pt", "eta", "jpt", "trig"),
        group_collections=("Electron", "Jet", None),
        group_weights=(None, "jpt", None),
    )


@pytest.mark.parametrize("E", [64, 257, 1000, 2048])
@pytest.mark.parametrize("K", [1, 4, 8])
def test_predicate_eval_sweep(E, K):
    prog = _program()
    terms = RNG.normal(20, 20, (4, E, K)).astype(np.float32)
    valid = (RNG.random((3, E, K)) < 0.5).astype(np.float32)
    weights = np.abs(RNG.normal(40, 20, (3, E, K))).astype(np.float32)
    got = np.asarray(ops.predicate_eval(terms, valid, weights, prog))
    want = np.asarray(
        ref.predicate_eval_ref(
            jnp.asarray(terms), jnp.asarray(valid), jnp.asarray(weights), prog
        )
    )
    np.testing.assert_array_equal(got.astype(bool), want)


@pytest.mark.parametrize("op", list(OP_IDS.values()))
def test_predicate_all_ops(op):
    prog = Program(
        groups=(Group(GROUP_COUNT, (0,), (op,), (5.0,)),),
        term_branches=("x",),
        group_collections=(None,),
        group_weights=(None,),
    )
    terms = RNG.normal(5, 5, (1, 256, 1)).astype(np.float32)
    valid = np.ones((1, 256, 1), np.float32)
    weights = np.zeros((1, 256, 1), np.float32)
    got = np.asarray(ops.predicate_eval(terms, valid, weights, prog))
    want = np.asarray(
        ref.predicate_eval_ref(
            jnp.asarray(terms), jnp.asarray(valid), jnp.asarray(weights), prog
        )
    )
    np.testing.assert_array_equal(got.astype(bool), want)


@pytest.mark.parametrize("E,D", [(128, 1), (512, 7), (1000, 16), (2048, 3)])
@pytest.mark.parametrize("rate", [0.0, 0.13, 0.5, 1.0])
def test_stream_compact_sweep(E, D, rate):
    payload = RNG.normal(size=(E, D)).astype(np.float32)
    mask = RNG.random(E) < rate
    packed, count = ops.stream_compact(payload, mask)
    wpacked, wcount = ref.stream_compact_ref(jnp.asarray(payload), jnp.asarray(mask))
    assert int(count) == int(wcount) == int(mask.sum())
    np.testing.assert_allclose(np.asarray(packed), np.asarray(wpacked), rtol=1e-6)


def test_stream_compact_preserves_order():
    E = 512
    payload = np.arange(E, dtype=np.float32)[:, None]
    mask = np.zeros(E, bool)
    mask[[3, 100, 101, 400]] = True
    packed, count = ops.stream_compact(payload, mask)
    np.testing.assert_array_equal(
        np.asarray(packed[:4, 0]), [3.0, 100.0, 101.0, 400.0]
    )
    assert np.all(np.asarray(packed[4:]) == 0)


@pytest.mark.parametrize(
    "dtype,gen",
    [
        (np.int32, lambda n: RNG.integers(-3000, 3000, n).astype(np.int32)),
        # smooth floats trigger the raw bail-out (kind 3, passthrough)
        (np.float32, lambda n: (RNG.exponential(30, n) + 1).astype(np.float32)),
        # discrete floats xor-compress -> exercises the KIND_FLOAT kernel path
        (
            np.float32,
            lambda n: RNG.choice(
                np.array([1.0, 1.25, 1.5, 1.75], np.float32), n
            ),
        ),
        (np.bool_, lambda n: RNG.random(n) < 0.2),
    ],
)
@pytest.mark.parametrize("sizes", [(64,), (100, 5000, 333), (4096, 4096)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_basket_decode_sweep(dtype, gen, sizes, use_pallas):
    """Jitted jnp mirror and the Pallas kernel (interpret mode here)."""
    arrs = [gen(n) for n in sizes]
    parts = [bitpack_raw_parts(bitpack_encode(a)) for a in arrs]
    out_dtype = jnp.int32 if dtype == np.int32 else jnp.float32
    outs = ops.basket_decode_batch(parts, [out_dtype] * len(parts), use_pallas=use_pallas)
    for a, o in zip(arrs, outs):
        np.testing.assert_array_equal(np.asarray(o), a.astype(np.asarray(o).dtype))


def test_basket_decode_matches_ref_kernel():
    arrs = [RNG.integers(-100, 100, 512).astype(np.int32) for _ in range(3)]
    parts = [bitpack_raw_parts(bitpack_encode(a)) for a in arrs]
    bits = max(p["bits"] for p in parts)
    W = max(p["n_pad"] for p in parts) // 32
    planes = np.zeros((3, bits, W), np.uint32)
    firsts = np.zeros(3, np.uint32)
    for i, p in enumerate(parts):
        pw = p["planes"].reshape(max(p["bits"], 1), -1)
        planes[i, : pw.shape[0], : pw.shape[1]] = pw
        firsts[i] = p["first"]
    want = ref.basket_decode_ref(
        jnp.asarray(planes), jnp.asarray(firsts), 0, 512, jnp.int32
    )
    for i, a in enumerate(arrs):
        np.testing.assert_array_equal(np.asarray(want[i]), a)


@pytest.mark.parametrize("B,H,S,D", [(1, 1, 128, 32), (2, 3, 256, 64), (1, 2, 512, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, S, D, causal):
    q, k, v = (
        RNG.normal(size=(B, H, S, D)).astype(np.float32) for _ in range(3)
    )
    got = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
    want = ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_flash_attention_bf16():
    q, k, v = (
        jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.bfloat16) for _ in range(3)
    )
    got = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0.05, atol=0.05
    )


@given(st.integers(1, 3), st.integers(1, 6), st.floats(0.05, 0.95))
@settings(max_examples=10, deadline=None)
def test_compact_count_property(d, seed, rate):
    rng = np.random.default_rng(seed)
    E = 256
    payload = rng.normal(size=(E, d)).astype(np.float32)
    mask = rng.random(E) < rate
    packed, count = ops.stream_compact(payload, mask)
    # survivor multiset preserved
    got = np.sort(np.asarray(packed[: int(count)]), axis=0)
    want = np.sort(payload[mask], axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
