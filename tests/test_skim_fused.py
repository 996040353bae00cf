"""Fused predicate+compact kernel vs the two-kernel oracle composition."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.predicate_eval import Group, Program
from repro.kernels.ref import GROUP_ANY, GROUP_COUNT, GROUP_HT, OP_IDS

RNG = np.random.default_rng(3)


def _program():
    return Program(
        groups=(
            Group(GROUP_COUNT, (0, 1), (OP_IDS[">"], OP_IDS["abs<"]), (20.0, 25.0)),
            Group(GROUP_HT, (2,), (OP_IDS[">"],), (10.0,),
                  cmp_op=OP_IDS[">"], cmp_thr=100.0),
            Group(GROUP_ANY, (3,), (OP_IDS[">="],), (0.5,)),
        ),
        term_branches=("a", "b", "c", "d"),
        group_collections=("X", None, None),
        group_weights=(None, "w", None),
    )


@pytest.mark.parametrize("E,K,D", [(256, 4, 3), (1000, 8, 6), (2048, 1, 1)])
def test_fused_matches_two_pass(E, K, D):
    prog = _program()
    terms = RNG.normal(20, 15, (4, E, K)).astype(np.float32)
    valid = (RNG.random((3, E, K)) < 0.4).astype(np.float32)
    weights = np.abs(RNG.normal(30, 20, (3, E, K))).astype(np.float32)
    payload = RNG.normal(size=(E, D)).astype(np.float32)

    packed, count = ops.skim_fused(terms, valid, weights, payload, prog)
    mask = ref.predicate_eval_ref(
        jnp.asarray(terms), jnp.asarray(valid), jnp.asarray(weights), prog
    )
    want_packed, want_count = ref.stream_compact_ref(jnp.asarray(payload), mask)
    assert int(count) == int(want_count)
    np.testing.assert_allclose(
        np.asarray(packed), np.asarray(want_packed), rtol=1e-6
    )


def test_fused_empty_and_full():
    prog = Program(
        groups=(Group(GROUP_COUNT, (0,), (OP_IDS[">"],), (0.0,)),),
        term_branches=("x",),
        group_collections=(None,),
        group_weights=(None,),
    )
    E = 512
    valid = np.ones((1, E, 1), np.float32)
    weights = np.zeros((1, E, 1), np.float32)
    payload = RNG.normal(size=(E, 2)).astype(np.float32)
    # all pass
    terms = np.ones((1, E, 1), np.float32)
    packed, count = ops.skim_fused(terms, valid, weights, payload, prog)
    assert int(count) == E
    np.testing.assert_allclose(np.asarray(packed), payload, rtol=1e-6)
    # none pass
    terms = -np.ones((1, E, 1), np.float32)
    packed, count = ops.skim_fused(terms, valid, weights, payload, prog)
    assert int(count) == 0
    assert np.all(np.asarray(packed) == 0)


def test_fused_batch_pallas_matches_oracle():
    """The window-batched Pallas kernel (interpret mode here) against the
    vmapped jnp oracle, window by window."""
    prog = _program()
    B, E, K, D = 3, 1024, 4, 3
    terms = RNG.normal(20, 15, (B, 4, E, K)).astype(np.float32)
    valid = (RNG.random((B, 3, E, K)) < 0.4).astype(np.float32)
    weights = np.abs(RNG.normal(30, 20, (B, 3, E, K))).astype(np.float32)
    payload = RNG.normal(size=(B, E, D)).astype(np.float32)
    got, got_n = ops.fused_skim_batch(terms, valid, weights, payload, prog, use_pallas=True)
    want, want_n = ops.fused_skim_batch(terms, valid, weights, payload, prog, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
