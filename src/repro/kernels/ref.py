"""Pure-jnp oracles for every Pallas kernel.

These are the semantics of record: each kernel's test sweeps shapes/dtypes
and asserts ``assert_allclose`` against the function here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.expr import (
    RPN_ABS,
    RPN_ADD,
    RPN_BRANCH,
    RPN_CONST,
    RPN_DIV,
    RPN_MAX,
    RPN_MIN,
    RPN_MUL,
    RPN_NEG,
    RPN_SUB,
    RPN_SUM,
)

# ---------------------------------------------------------------------------
# predicate_eval
# ---------------------------------------------------------------------------

OP_GT, OP_GE, OP_LT, OP_LE, OP_EQ, OP_NE, OP_ABSLT, OP_ABSGT = range(8)

OP_IDS = {
    ">": OP_GT,
    ">=": OP_GE,
    "<": OP_LT,
    "<=": OP_LE,
    "==": OP_EQ,
    "!=": OP_NE,
    "abs<": OP_ABSLT,
    "abs>": OP_ABSGT,
}

GROUP_COUNT = 0  # count of objects passing all terms >= min_count
GROUP_HT = 1  # sum(weight * passing) cmp threshold
GROUP_ANY = 2  # OR over terms (flat boolean branches)
GROUP_MASS = 3  # leading-pair invariant mass inside [cmp_thr, cmp_thr2]
GROUP_DR = 4  # leading-pair ΔR cmp threshold
GROUP_EXPR = 5  # arithmetic stack program (Group.rpn) cmp threshold


def apply_op(x, op_id: int, thr: float):
    if op_id == OP_GT:
        return x > thr
    if op_id == OP_GE:
        return x >= thr
    if op_id == OP_LT:
        return x < thr
    if op_id == OP_LE:
        return x <= thr
    if op_id == OP_EQ:
        return x == thr
    if op_id == OP_NE:
        return x != thr
    if op_id == OP_ABSLT:
        return jnp.abs(x) < thr
    if op_id == OP_ABSGT:
        return jnp.abs(x) > thr
    raise ValueError(op_id)


def _lead_onehot(masked_pt: jnp.ndarray) -> jnp.ndarray:
    """(E, K) one-hot of each event's first maximal slot (ties -> lowest
    slot, i.e. storage order — the host lexsort tiebreak)."""
    i1 = jnp.argmax(masked_pt, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, masked_pt.shape, 1)
    return iota == i1[:, None]


def _pair_onehots(pt_a, va, pt_b, vb, same: bool):
    """Leading-pair selection: (oh1, oh2, ok).  Same-collection pairs take
    the two highest-pt objects of A; otherwise each collection's leading
    object.  ``ok`` marks events with a full pair (selection one-hots are
    garbage where it is False)."""
    neg = jnp.float32(-jnp.inf)
    ma = jnp.where(va, pt_a, neg)
    oh1 = _lead_onehot(ma)
    if same:
        oh2 = _lead_onehot(jnp.where(oh1, neg, ma))
        ok = va.astype(jnp.int32).sum(axis=-1) >= 2
    else:
        oh2 = _lead_onehot(jnp.where(vb, pt_b, neg))
        ok = (va.astype(jnp.int32).sum(axis=-1) >= 1) & (
            vb.astype(jnp.int32).sum(axis=-1) >= 1
        )
    return oh1, oh2, ok


def _sel(x: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """Select the one-hot slot of each event row: (E, K) -> (E,)."""
    return jnp.where(onehot, x, 0.0).sum(axis=-1)


def _unpack_validity(vg: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mass/ΔR groups pack two collections' validity planes into one float
    channel: bit0 = first collection, bit1 = second (values 0..3)."""
    return jnp.mod(vg, 2.0) >= 1.0, vg >= 2.0


def _pair_mass(a, b):
    """Invariant mass of two objects given as (pt, eta, phi, mass) —
    mirrored term-for-term by the float64 host helper
    (repro.core.expr.leading_pair_mass).

    Written without catastrophic cancellation, so the float32 result
    stays within a few ulp of float64:
    m² = m1² + m2² + 2(E1E2 − pz1pz2 − pT1·pT2), with E1E2 − pz1pz2
    taken as (mT1²mT2² + mT1²pz2² + mT2²pz1²) / (E1E2 + pz1pz2) when
    pz1pz2 > 0 (two boosted objects, E ≈ |pz|).  The textbook
    (E1+E2)² − |p1+p2|² loses ~4 digits there.  sinh is spelled
    through ``exp``, which the TPU kernel compiler lowers."""
    def kin(pt, eta, phi, mass):
        pz = pt * (0.5 * (jnp.exp(eta) - jnp.exp(-eta)))
        mtsq = mass * mass + pt * pt
        return pt * jnp.cos(phi), pt * jnp.sin(phi), pz, mtsq, jnp.sqrt(mtsq + pz * pz)

    px1, py1, pz1, mtsq1, e1 = kin(*a)
    px2, py2, pz2, mtsq2, e2 = kin(*b)
    ee, zz = e1 * e2, pz1 * pz2
    den = ee + zz
    boosted = (mtsq1 * mtsq2 + mtsq1 * pz2 * pz2 + mtsq2 * pz1 * pz1) / jnp.where(den > 0, den, 1.0)
    long_ = jnp.where(zz > 0, boosted, ee - zz)
    m2 = a[3] * a[3] + b[3] * b[3] + 2.0 * (long_ - (px1 * px2 + py1 * py2))
    return jnp.sqrt(jnp.maximum(m2, 0.0))


def _group_mass(grp, terms, vg, same: bool):
    ids = grp.term_ids  # (ptA, etaA, phiA, massA, ptB, etaB, phiB, massB)
    va, vb = _unpack_validity(vg)
    oh1, oh2, ok = _pair_onehots(terms[ids[0]], va, terms[ids[4]], vb, same)
    mass = _pair_mass(
        [_sel(terms[i], oh1) for i in ids[:4]],
        [_sel(terms[i], oh2) for i in ids[4:]],
    )
    return ok & (mass >= grp.cmp_thr) & (mass <= grp.cmp_thr2)


def _group_dr(grp, terms, vg, same: bool):
    ids = grp.term_ids  # (ptA, etaA, phiA, ptB, etaB, phiB)
    va, vb = _unpack_validity(vg)
    oh1, oh2, ok = _pair_onehots(terms[ids[0]], va, terms[ids[3]], vb, same)
    deta = _sel(terms[ids[1]], oh1) - _sel(terms[ids[4]], oh2)
    pi = jnp.float32(np.pi)
    dphi = jnp.mod(
        _sel(terms[ids[2]], oh1) - _sel(terms[ids[5]], oh2) + pi, 2.0 * pi
    ) - pi
    dr = jnp.sqrt(deta * deta + dphi * dphi)
    return ok & apply_op(dr, grp.cmp_op, grp.cmp_thr)


def _group_expr(grp, terms):
    """Stack-program evaluation over term slots: flat branches read slot 0,
    sum() reductions sum the zero-padded slots (invalid slots are exactly
    0.0 by the ingest contract, so no validity channel is needed)."""
    stack: list = []
    for op, arg in grp.rpn:
        if op == RPN_BRANCH:
            stack.append(terms[int(arg)][:, 0])
        elif op == RPN_SUM:
            stack.append(terms[int(arg)].sum(axis=-1))
        elif op == RPN_CONST:
            stack.append(jnp.float32(arg))
        elif op == RPN_NEG:
            stack.append(-stack.pop())
        elif op == RPN_ABS:
            stack.append(jnp.abs(stack.pop()))
        else:
            b = stack.pop()
            a = stack.pop()
            if op == RPN_ADD:
                stack.append(a + b)
            elif op == RPN_SUB:
                stack.append(a - b)
            elif op == RPN_MUL:
                stack.append(a * b)
            elif op == RPN_DIV:
                stack.append(a / b)
            elif op == RPN_MIN:
                stack.append(jnp.minimum(a, b))
            elif op == RPN_MAX:
                stack.append(jnp.maximum(a, b))
            else:
                raise ValueError(f"unknown RPN op {op}")
    return apply_op(stack[-1], grp.cmp_op, grp.cmp_thr)


def _coll2(program, g: int):
    c2 = getattr(program, "group_collections2", ())
    return c2[g] if c2 else None


def predicate_mask(program, terms, valid, weights) -> jnp.ndarray:
    """Evaluate a compiled predicate program (the single body shared by
    this oracle, the Pallas predicate kernel, and the fused kernel).

    Args:
      terms:   (T, E, K) float32 — per-term padded values.
      valid:   (G, E, K) float — per-group object validity (mass/ΔR groups
               carry two packed planes, see ``_unpack_validity``).
      weights: (G, E, K) float32 — per-group HT weights (zeros if unused).
      program: static description (see kernels.predicate_eval.Program).
    Returns: (E,) bool event mask.
    """
    E = terms.shape[1]
    mask = jnp.ones((E,), dtype=bool)
    for g, grp in enumerate(program.groups):
        if grp.kind == GROUP_ANY:
            gpass = jnp.zeros((E,), dtype=bool)
            for t, op, thr in zip(grp.term_ids, grp.ops, grp.thrs):
                gpass = gpass | apply_op(terms[t, :, 0], op, thr)
        elif grp.kind == GROUP_EXPR:
            gpass = _group_expr(grp, terms)
        elif grp.kind in (GROUP_MASS, GROUP_DR):
            same = program.group_collections[g] == _coll2(program, g)
            fn = _group_mass if grp.kind == GROUP_MASS else _group_dr
            gpass = fn(grp, terms, valid[g], same)
        else:
            obj = jnp.ones(terms.shape[1:], dtype=bool)  # (E, K)
            for t, op, thr in zip(grp.term_ids, grp.ops, grp.thrs):
                obj = obj & apply_op(terms[t], op, thr)
            obj = obj & (valid[g] > 0)
            if grp.kind == GROUP_COUNT:
                gpass = obj.astype(jnp.int32).sum(axis=-1) >= grp.min_count
            elif grp.kind == GROUP_HT:
                ht = (weights[g] * obj.astype(jnp.float32)).sum(axis=-1)
                gpass = apply_op(ht, grp.cmp_op, grp.cmp_thr)
            else:
                raise ValueError(grp.kind)
        mask = mask & gpass
    return mask


def predicate_eval_ref(terms, valid, weights, program) -> jnp.ndarray:
    """Oracle alias of :func:`predicate_mask` (the semantics of record)."""
    return predicate_mask(program, terms, valid, weights)


# ---------------------------------------------------------------------------
# stream_compact
# ---------------------------------------------------------------------------


def stream_compact_ref(payload: jnp.ndarray, mask: jnp.ndarray):
    """Pack rows of ``payload`` where ``mask`` is true to the front.

    Returns (packed (E, D) with survivors first then zeros, count ()).
    """
    E = payload.shape[0]
    mask = mask.astype(bool)
    order = jnp.argsort(~mask, stable=True)  # survivors first, stable
    packed = payload[order]
    count = mask.sum(dtype=jnp.int32)
    keep = jnp.arange(E) < count
    packed = jnp.where(keep[:, None], packed, 0)
    return packed, count


# ---------------------------------------------------------------------------
# basket_decode
# ---------------------------------------------------------------------------


def basket_decode_ref(planes, firsts, kind: int, n_values: int, out_dtype):
    """Decode a batch of bit-plane baskets.

    Args:
      planes: (N, B, W) uint32 — B bit-planes of W words per basket
              (planes above the basket's true bit width are zero).
      firsts: (N,) uint32 — first raw value (bit pattern).
      kind:   static int — 0 int-delta, 1 float-xor, 2 bool.
      n_values: static — values per basket (W*32 >= n_values).
    Returns: (N, n_values) array of ``out_dtype``.
    """
    N, B, W = planes.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    codes = jnp.zeros((N, W * 32), dtype=jnp.uint32)
    for j in range(B):
        bits = (planes[:, j, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
        codes = codes | (bits.reshape(N, W * 32) << jnp.uint32(j))
    codes = codes[:, :n_values]

    if kind == 2:  # bool
        return codes.astype(out_dtype)
    if kind == 0:  # zigzag delta + cumsum
        u = codes.astype(jnp.uint32)
        dec = (u >> 1).astype(jnp.int32) ^ -(u & 1).astype(jnp.int32)
        first = jax.lax.bitcast_convert_type(firsts.astype(jnp.uint32), jnp.int32)
        dec = dec.at[:, 0].set(first)
        return jnp.cumsum(dec, axis=1).astype(out_dtype)
    if kind == 1:  # xor prefix + bitcast
        codes = codes.at[:, 0].set(firsts)
        acc = jax.lax.associative_scan(jnp.bitwise_xor, codes, axis=1)
        return jax.lax.bitcast_convert_type(acc, jnp.float32).astype(out_dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


def flash_attention_ref(q, k, v, causal: bool = True, sm_scale: float | None = None):
    """(B, H, S, D) reference attention; fp32 accumulation."""
    B, H, S, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs.astype(v.dtype), v)
