"""Pallas predicate-evaluation kernel (TPU target, interpret-validated).

The TPU analogue of SkimROOT's on-DPU filtering loop: a query's selection
criteria are compiled to a static *program* (term comparisons + group
reductions) and evaluated over VMEM tiles of padded columnar event data.
All thresholds/ops are baked into the kernel closure, so the inner body is
pure vector compares + reductions on the VPU — one pass over each basket.

Data layout (device path): events are dense tiles, collections padded to a
static ``K`` objects/event with a validity mask — the jagged->padded
conversion happens once at ingest (``repro.core.neardata``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.expr import KINEMATIC_VARS, RPN_BRANCH, RPN_SUM
from repro.kernels.ref import (
    GROUP_ANY,
    GROUP_COUNT,
    GROUP_DR,
    GROUP_EXPR,
    GROUP_HT,
    GROUP_MASS,
    OP_IDS,
    predicate_mask,
)

EVENT_TILE = 1024  # events per grid step; multiple of 8*128 lanes

# VMEM sizing.  Object slots K sit on the 128-wide lane axis, so a block
# row of K <= 128 floats occupies a full 512-byte lane row; the input
# blocks of one grid step (T term planes + G validity + G weight planes,
# double-buffered) are held under _BLOCK_BUDGET by shrinking the event
# tile, and the scoped limit leaves room for the body's (tile, K)
# temporaries (the mass group keeps a few dozen alive).
_BLOCK_BUDGET = 4 << 20
_MIN_TILE = 128  # mask blocks must stay a multiple of 128 lanes
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 << 20)


def fit_event_tile(event_tile: int, planes: int, K: int) -> int:
    """Largest tile <= ``event_tile`` (halving) whose double-buffered
    input blocks of ``planes`` (tile, K) float planes fit the budget."""
    row_bytes = 4 * 128 * -(-K // 128)
    while event_tile > _MIN_TILE and 2 * planes * event_tile * row_bytes > _BLOCK_BUDGET:
        event_tile //= 2
    return event_tile


@dataclass(frozen=True)
class Group:
    kind: int  # GROUP_COUNT / GROUP_HT / GROUP_ANY / GROUP_MASS / ...
    term_ids: tuple[int, ...]
    ops: tuple[int, ...]
    thrs: tuple[float, ...]
    min_count: int = 1
    cmp_op: int = 0
    cmp_thr: float = 0.0
    cmp_thr2: float = 0.0  # mass window upper bound (GROUP_MASS)
    rpn: tuple = ()  # GROUP_EXPR stack program, term-slot operands


@dataclass(frozen=True)
class Program:
    """Static predicate program: ``T`` terms over ``G`` AND-ed groups."""

    groups: tuple[Group, ...]
    term_branches: tuple[str, ...]  # branch feeding each term slot
    group_collections: tuple[str | None, ...]  # validity source per group
    group_weights: tuple[str | None, ...]  # HT weight branch per group
    # second collection of mass/ΔR pair groups (None elsewhere); default ()
    # keeps hand-built three-field programs (tests, older callers) valid
    group_collections2: tuple = ()

    @property
    def n_terms(self) -> int:
        return len(self.term_branches)

    @property
    def n_groups(self) -> int:
        return len(self.groups)


def compile_query(query) -> Program:
    """Lower a :class:`repro.core.query.Query` to a :class:`Program`.

    Compilation is store-independent (the cluster coordinator compiles
    once and fans out to shards with possibly different schemas): trigger
    branches absent from a store evaluate as constant-False at ingest
    (zero term pages), not here.
    """
    from repro.core.query import (
        AnyOf,
        Cut,
        DeltaRCut,
        ExprCut,
        HTCut,
        MassWindow,
        ObjectSelection,
    )

    term_branches: list[str] = []
    groups: list[Group] = []
    group_colls: list[str | None] = []
    group_colls2: list[str | None] = []
    group_weights: list[str | None] = []

    def add_term(branch: str) -> int:
        term_branches.append(branch)
        return len(term_branches) - 1

    def add_group(group: Group, coll=None, coll2=None, weight=None) -> None:
        groups.append(group)
        group_colls.append(coll)
        group_colls2.append(coll2)
        group_weights.append(weight)

    for _, stage in query.stages():
        for node in stage:
            if isinstance(node, Cut):
                t = add_term(node.branch)
                add_group(
                    Group(GROUP_COUNT, (t,), (OP_IDS[node.op],), (float(node.value),))
                )
            elif isinstance(node, AnyOf):
                ids = tuple(add_term(n) for n in node.names)
                add_group(
                    Group(GROUP_ANY, ids, (OP_IDS[">="],) * len(ids), (0.5,) * len(ids))
                )
            elif isinstance(node, ObjectSelection):
                ids, ops, thrs = [], [], []
                for c in node.cuts:
                    ids.append(add_term(f"{node.collection}_{c.var}"))
                    ops.append(OP_IDS[c.op])
                    thrs.append(float(c.value))
                add_group(
                    Group(
                        GROUP_COUNT,
                        tuple(ids),
                        tuple(ops),
                        tuple(thrs),
                        min_count=node.min_count,
                    ),
                    coll=node.collection,
                )
            elif isinstance(node, HTCut):
                ids, ops, thrs = [], [], []
                for c in node.object_cuts:
                    ids.append(add_term(f"{node.collection}_{c.var}"))
                    ops.append(OP_IDS[c.op])
                    thrs.append(float(c.value))
                if not ids:  # unconditioned HT still needs a term for shape
                    ids.append(add_term(f"{node.collection}_{node.var}"))
                    ops.append(OP_IDS[">="])
                    thrs.append(-jnp.inf)
                add_group(
                    Group(
                        GROUP_HT,
                        tuple(ids),
                        tuple(ops),
                        tuple(thrs),
                        cmp_op=OP_IDS[node.op],
                        cmp_thr=float(node.value),
                    ),
                    coll=node.collection,
                    weight=f"{node.collection}_{node.var}",
                )
            elif isinstance(node, MassWindow):
                a, b = node.collections
                ids = tuple(
                    add_term(f"{c}_{v}")
                    for c in (a, b)
                    for v in KINEMATIC_VARS["mass"]
                )
                add_group(
                    Group(
                        GROUP_MASS, ids, (), (),
                        cmp_thr=float(node.lo), cmp_thr2=float(node.hi),
                    ),
                    coll=a, coll2=b,
                )
            elif isinstance(node, DeltaRCut):
                a, b = node.collections
                ids = tuple(
                    add_term(f"{c}_{v}")
                    for c in (a, b)
                    for v in KINEMATIC_VARS["deltaR"]
                )
                add_group(
                    Group(
                        GROUP_DR, ids, (), (),
                        cmp_op=OP_IDS[node.op], cmp_thr=float(node.value),
                    ),
                    coll=a, coll2=b,
                )
            elif isinstance(node, ExprCut):
                # rewrite branch-name operands to term slots; sums read the
                # zero-padded object slots, flat refs read slot 0
                rpn = []
                ids = []
                for op, arg in node.rpn:
                    if op in (RPN_BRANCH, RPN_SUM):
                        t = add_term(str(arg))
                        ids.append(t)
                        rpn.append((op, t))
                    else:
                        rpn.append((op, arg))
                add_group(
                    Group(
                        GROUP_EXPR, tuple(ids), (), (),
                        cmp_op=OP_IDS[node.op], cmp_thr=float(node.value),
                        rpn=tuple(rpn),
                    )
                )
            else:
                raise TypeError(f"cannot compile node {type(node)}")

    program = Program(
        tuple(groups),
        tuple(term_branches),
        tuple(group_colls),
        tuple(group_weights),
        tuple(group_colls2),
    )
    # static verification gate (REPRO_VERIFY=1): prove the compiled
    # program's structural invariants before anything evaluates it
    from repro.analysis.verify import maybe_verify_program

    maybe_verify_program(program)
    return program


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _predicate_kernel(terms_ref, valid_ref, weights_ref, out_ref, *, program: Program):
    """One event tile: terms (T, Eb, K), valid (G, Eb, K), weights (G, Eb, K).

    The evaluation body is :func:`repro.kernels.ref.predicate_mask` — one
    implementation shared with the oracle and the fused kernel, so every
    group kind (count/HT/trigger-OR/mass/ΔR/expr) behaves identically
    across the three."""
    mask = predicate_mask(
        program, terms_ref[...], valid_ref[...], weights_ref[...]
    )
    out_ref[...] = mask.astype(jnp.int32).reshape(out_ref.shape)


def _predicate_kernel_batched(terms_ref, valid_ref, weights_ref, out_ref,
                              *, program: Program):
    """Window-batched body: blocks carry a leading window dim of 1."""
    mask = predicate_mask(
        program, terms_ref[0], valid_ref[0], weights_ref[0]
    )
    out_ref[...] = mask.astype(jnp.int32).reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("program", "interpret", "event_tile"))
def predicate_eval_batch(
    terms: jnp.ndarray,
    valid: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    program: Program,
    interpret: bool = True,
    event_tile: int = EVENT_TILE,
) -> jnp.ndarray:
    """Window-batched predicate evaluation: ONE dispatch per batch.

    ``terms`` (B, T, E, K), ``valid``/``weights`` (B, G, E, K); the grid
    runs (B, E/tile) with the window axis outermost.  Returns (B, E)
    int32 survivor masks — the device-resident mask source of the
    batched cascade (DESIGN.md §16).
    """
    Bn, T, E, K = terms.shape
    G = valid.shape[1]
    event_tile = fit_event_tile(event_tile, T + 2 * G, K)
    assert E % event_tile == 0, (E, event_tile)
    grid = (Bn, E // event_tile)

    # the mask leaves as (B, 1, E): a (1, 1, tile) block keeps the window
    # row off the sublane axis, where a 1-row block breaks the (8, 128) rule
    out = pl.pallas_call(
        functools.partial(_predicate_kernel_batched, program=program),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T, event_tile, K), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, event_tile, K), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, G, event_tile, K), lambda b, i: (b, 0, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, event_tile), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((Bn, 1, E), jnp.int32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(terms, valid, weights)
    return out.reshape(Bn, E)


@functools.partial(jax.jit, static_argnames=("program", "interpret", "event_tile"))
def predicate_eval(
    terms: jnp.ndarray,
    valid: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    program: Program,
    interpret: bool = True,
    event_tile: int = EVENT_TILE,
) -> jnp.ndarray:
    """Evaluate the predicate program; returns (E,) int32 survivor mask.

    ``terms`` (T, E, K) float32, ``valid``/``weights`` (G, E, K).  ``E``
    must be a multiple of ``event_tile`` (the ingest path pads), which is
    an upper bound: wide programs run a smaller tile (:func:`fit_event_tile`).
    """
    T, E, K = terms.shape
    G = valid.shape[0]
    event_tile = fit_event_tile(event_tile, T + 2 * G, K)
    assert E % event_tile == 0, (E, event_tile)
    grid = (E // event_tile,)

    # (1, E) out: a rank-1 block would have to match XLA's 1024-lane tiling
    out = pl.pallas_call(
        functools.partial(_predicate_kernel, program=program),
        grid=grid,
        in_specs=[
            pl.BlockSpec((T, event_tile, K), lambda i: (0, i, 0)),
            pl.BlockSpec((G, event_tile, K), lambda i: (0, i, 0)),
            pl.BlockSpec((G, event_tile, K), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, event_tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, E), jnp.int32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(terms, valid, weights)
    return out.reshape(E)
