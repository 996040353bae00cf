"""Near-data skimming on the accelerator mesh (DESIGN.md §2, §6).

The paper's placement insight — filter where the bytes live, ship only
survivors — mapped to a JAX mesh: events are sharded over the ``data``
(and ``pod``) axes; each shard evaluates the compiled predicate and
compacts its survivors locally inside ``shard_map``; only compacted
survivor payloads ever cross the interconnect.

Device data layout: jagged collections are padded to a static ``K``
objects/event with a validity mask (built once at ingest by
:func:`build_padded_inputs`), so the device path is dense tiles — exactly
what the Pallas kernels want.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import expr as xpr
from repro.kernels import ref as kref
from repro.kernels.predicate_eval import Program, compile_query
from repro.obs.trace import NULL_TRACER


@dataclass
class PaddedBatch:
    """Dense device-side event batch for predicate evaluation."""

    terms: jnp.ndarray  # (T, E, K) float32
    valid: jnp.ndarray  # (G, E, K) float32
    weights: jnp.ndarray  # (G, E, K) float32
    payload: jnp.ndarray  # (E, D) float32 — output columns to compact
    n_events: int


def _scatter_jagged(out: np.ndarray, values: np.ndarray, counts: np.ndarray) -> None:
    """Write jagged values into a preallocated (E, K) dense view (in place;
    fully vectorized — this runs per window on the skim hot path)."""
    E, K = out.shape
    take = np.minimum(counts, K).astype(np.int64)
    if not (E and take.sum()):
        return
    offsets = np.concatenate([[0], np.cumsum(counts)])
    idx_event = np.repeat(np.arange(E), take)
    # slot index within each event: global ramp minus each event's base
    bases = np.concatenate([[0], np.cumsum(take)])[:-1]
    idx_slot = np.arange(take.sum()) - np.repeat(bases, take)
    src_idx = np.repeat(offsets[:-1], take) + idx_slot
    out[idx_event, idx_slot] = values[src_idx].astype(np.float32)


def _collection_validity(counts: np.ndarray, K: int) -> np.ndarray:
    """(E, K) validity: slot k live iff k < counts[e]."""
    take = np.minimum(counts, K)
    return (np.arange(K)[None, :] < take[:, None]).astype(np.float32)


def build_padded_inputs(
    data: dict[str, np.ndarray],
    program: Program,
    store,
    K: int = 8,
    payload_branches: list[str] | None = None,
    include_index: bool = False,
    to_device: bool = True,
) -> PaddedBatch:
    """Build dense kernel inputs from columnar (host) data.

    ``data`` is the decoded columnar dict (flat arrays; jagged values with
    their ``n<Coll>`` counts).  ``K`` caps objects/event (overflow objects
    are dropped from *filtering only* — counts-based cuts use true counts
    via validity, see below).

    ``include_index=True`` prepends a local-event-index column to the
    payload: after stream compaction the survivor rows carry their own
    source indices, so the host can reconstruct the boolean mask from the
    compacted output alone — the mask itself never has to leave the device
    (DESIGN.md §7).  float32 holds indices exactly up to 2**24 events,
    far above any window size.
    """
    flat_names = [n for n in data if not (store.branches.get(n) and store.branches[n].jagged)]
    n_events = len(data[flat_names[0]])

    T = program.n_terms
    G = program.n_groups
    # preallocate and fill views in place: flat branches touch only slot 0
    # of their zero pages, jagged branches scatter exactly once — this is
    # the per-window hot path of the fused executor
    terms = np.zeros((T, n_events, K), np.float32)
    valid = np.zeros((G, n_events, K), np.float32)
    weights = np.zeros((G, n_events, K), np.float32)

    values_cache: dict[str, np.ndarray] = {}  # scatter each branch once

    def fill_values(target: np.ndarray, branch: str) -> None:
        if branch not in data:
            # absent trigger branch (menus differ across eras): the zero
            # page is constant-False under the ANY-group >= 0.5 test; the
            # planner guarantees every non-optional branch is present
            return
        br = store.branches.get(branch)
        if br is not None and br.jagged:
            if branch not in values_cache:
                _scatter_jagged(
                    target,
                    np.asarray(data[branch]),
                    np.asarray(data[br.counts_branch], dtype=np.int64),
                )
                values_cache[branch] = target
            else:
                np.copyto(target, values_cache[branch])
        else:
            target[:, 0] = np.asarray(data[branch], dtype=np.float32)

    validity_cache: dict[str, np.ndarray] = {}  # keyed by counts branch

    def validity_of(branch: str) -> np.ndarray:
        br = store.branches.get(branch)
        key = br.counts_branch if (br is not None and br.jagged) else ""
        if key not in validity_cache:
            if key:  # one validity per collection, shared by its branches
                validity_cache[key] = _collection_validity(
                    np.asarray(data[key], dtype=np.int64), K
                )
            else:  # flat branches live in slot 0 only
                v = np.zeros((n_events, K), np.float32)
                v[:, 0] = 1.0
                validity_cache[key] = v
        return validity_cache[key]

    for t, branch in enumerate(program.term_branches):
        fill_values(terms[t], branch)
    for g, grp in enumerate(program.groups):
        if grp.kind in (kref.GROUP_MASS, kref.GROUP_DR):
            # pair groups read two collections: pack both validity planes
            # into the one channel (bit0 = first, bit1 = second; a
            # same-collection pair encodes 3 everywhere it has objects)
            half = len(grp.term_ids) // 2
            first = program.term_branches[grp.term_ids[0]]
            second = program.term_branches[grp.term_ids[half]]
            valid[g] = validity_of(first) + 2.0 * validity_of(second)
            continue
        if grp.kind == kref.GROUP_EXPR:
            # sum() reductions read the zero-padded object slots directly
            # (invalid slots are exactly 0.0) — no validity channel
            continue
        if grp.term_ids:
            anchor = program.term_branches[grp.term_ids[0]]
            valid[g] = validity_of(anchor)
        wbranch = program.group_weights[g]
        if wbranch is not None:
            fill_values(weights[g], wbranch)

    payload_branches = payload_branches or []
    pay_cols = []
    if include_index:
        if n_events >= 1 << 24:
            raise ValueError("window too large for exact float32 index payload")
        pay_cols.append(np.arange(n_events, dtype=np.float32))
    pay_cols.extend(np.asarray(data[b], dtype=np.float32) for b in payload_branches)
    if pay_cols:
        payload = np.stack(pay_cols, axis=1)
    else:
        payload = np.zeros((n_events, 1), np.float32)

    if not to_device:
        # batched staging keeps host buffers: the caller places windows at
        # span offsets inside a batch tensor and ships the batch once
        return PaddedBatch(
            terms=terms, valid=valid, weights=weights,
            payload=payload, n_events=n_events,
        )
    return PaddedBatch(
        terms=jnp.asarray(terms),
        valid=jnp.asarray(valid),
        weights=jnp.asarray(weights),
        payload=jnp.asarray(payload),
        n_events=n_events,
    )


# ---------------------------------------------------------------------------
# device-side evaluation
# ---------------------------------------------------------------------------


def skim_mask(batch_terms, batch_valid, batch_weights, program: Program):
    """jnp predicate path (works on any backend; Pallas path in kernels.ops)."""
    return kref.predicate_eval_ref(batch_terms, batch_valid, batch_weights, program)


# numpy mirror of kernels.ref.apply_op, keyed by the compiled op ids
_NP_OPS = {
    kref.OP_GT: np.greater,
    kref.OP_GE: np.greater_equal,
    kref.OP_LT: np.less,
    kref.OP_LE: np.less_equal,
    kref.OP_EQ: np.equal,
    kref.OP_NE: np.not_equal,
    kref.OP_ABSLT: lambda x, v: np.abs(x) < v,
    kref.OP_ABSGT: lambda x, v: np.abs(x) > v,
}


def program_eval_np(
    data: dict[str, np.ndarray], program: Program, n_events: int
) -> np.ndarray:
    """Host interpreter for a compiled :class:`Program` over the *jagged*
    columnar layout (no padding).

    This is the fused executor's CPU fallback: one pass over the compiled
    groups, semantically identical to ``repro.core.query.eval_stage`` run
    over every stage (same float64 segment accumulation, so masks are
    bit-identical to the reference path) and to the device kernels modulo
    their float32 reductions.  On jagged data it skips the (T, E, K)
    densification entirely, which is what makes ``fused=True`` at least
    as fast as the staged evaluator on backends without a real
    accelerator.
    """
    mask = np.ones(n_events, dtype=bool)
    for g, grp in enumerate(program.groups):
        coll = program.group_collections[g]
        if grp.kind == kref.GROUP_ANY:
            gpass = np.zeros(n_events, dtype=bool)
            for t, op, thr in zip(grp.term_ids, grp.ops, grp.thrs):
                arr = data.get(program.term_branches[t])
                if arr is None:
                    continue  # absent trigger branch: constant-False
                gpass |= np.asarray(_NP_OPS[op](arr, thr), dtype=bool)
        elif grp.kind == kref.GROUP_MASS:
            m, ok = xpr.leading_pair_mass(
                data, coll, program.group_collections2[g]
            )
            gpass = ok & (m >= grp.cmp_thr) & (m <= grp.cmp_thr2)
        elif grp.kind == kref.GROUP_PAIR_MASS:
            ev, m, qq = xpr.all_pair_masses(data, coll)
            ok = np.ones(len(qq), dtype=bool)
            for op, thr in zip(grp.ops, grp.thrs):
                ok &= np.asarray(_NP_OPS[op](qq, thr), dtype=bool)
            gpass = xpr.any_pair_in_window(
                ev, m, ok, grp.cmp_thr, grp.cmp_thr2, n_events
            )
        elif grp.kind == kref.GROUP_DR:
            dr, ok = xpr.leading_delta_r(
                data, coll, program.group_collections2[g]
            )
            gpass = ok & np.asarray(
                _NP_OPS[grp.cmp_op](dr, grp.cmp_thr), dtype=bool
            )
        elif grp.kind == kref.GROUP_EXPR:
            # same stack walk as the staged evaluator (expr.eval_rpn), with
            # term slots resolved back to branch names — bit-identical to
            # eval_node by construction
            def resolve(op, slot):
                name = program.term_branches[int(slot)]
                if op == xpr.RPN_BRANCH:
                    return np.asarray(data[name], dtype=np.float64)
                counts = np.asarray(
                    data[xpr.counts_name(name)], dtype=np.int64
                )
                return np.bincount(
                    np.repeat(np.arange(n_events), counts),
                    weights=np.asarray(data[name], dtype=np.float64),
                    minlength=n_events,
                )

            val = xpr.eval_rpn(grp.rpn, resolve)
            gpass = np.asarray(
                _NP_OPS[grp.cmp_op](val, grp.cmp_thr), dtype=bool
            )
        elif coll is None:
            # flat-branch cut compiled as a one-term COUNT group
            t, op, thr = grp.term_ids[0], grp.ops[0], grp.thrs[0]
            passing = np.asarray(
                _NP_OPS[op](data[program.term_branches[t]], thr), dtype=bool
            )
            gpass = passing.astype(np.int64) >= grp.min_count
        else:
            counts = np.asarray(data[f"n{coll}"], dtype=np.int64)
            ids = np.repeat(np.arange(n_events), counts)
            passing = np.ones(int(counts.sum()), dtype=bool)
            for t, op, thr in zip(grp.term_ids, grp.ops, grp.thrs):
                passing &= np.asarray(
                    _NP_OPS[op](data[program.term_branches[t]], thr), dtype=bool
                )
            if grp.kind == kref.GROUP_COUNT:
                # integer accumulation — exact counts, matching both the
                # staged evaluator and the device kernels' int32 path
                per_event = np.bincount(ids[passing], minlength=n_events)
                gpass = per_event >= grp.min_count
            else:  # GROUP_HT
                w = np.asarray(data[program.group_weights[g]], dtype=np.float64)
                ht = np.bincount(ids, weights=w * passing, minlength=n_events)
                gpass = np.asarray(
                    _NP_OPS[grp.cmp_op](ht, grp.cmp_thr), dtype=bool
                )
        mask &= gpass
    return mask


def compact_jnp(payload: jnp.ndarray, mask: jnp.ndarray):
    return kref.stream_compact_ref(payload, mask)


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def window_pad_K(data: dict[str, np.ndarray], program: Program, store) -> int:
    """Smallest pow2 object capacity that loses no object of any jagged
    branch the program reads — guarantees the padded device evaluation is
    bit-identical to the host evaluator (no overflow truncation)."""
    K = 1
    seen: set[str] = set()
    branches = set(program.term_branches) | {
        w for w in program.group_weights if w is not None
    }
    for name in branches:
        br = store.branches.get(name)
        if br is None or not br.jagged or br.counts_branch in seen:
            continue
        seen.add(br.counts_branch)
        counts = np.asarray(data[br.counts_branch])
        if len(counts):
            K = max(K, int(counts.max()))
    return _next_pow2(K)


_WINDOW_QUANTUM = 512  # event-axis padding multiple (fused kernel tile)


def pair_work(program: Program, counts_of, events: int, K: int) -> dict:
    """Span attributes of a launch that evaluates ``program``'s pair
    groups over ``events`` event rows of capacity ``K``: ``pair_slots``,
    the K(K−1)/2 slot pairs of every row, padding included, and
    ``pairs_live``, the n(n−1)/2 pairs of real objects, from each pair
    group's counts branch (``counts_of(name)`` -> the real events'
    counts).  Empty for a program without pair groups, so other launches
    carry no such attributes."""
    colls = [
        program.group_collections[g]
        for g, grp in enumerate(program.groups)
        if grp.kind == kref.GROUP_PAIR_MASS
    ]
    if not colls:
        return {}
    live = 0
    for coll in colls:
        n = np.asarray(counts_of(f"n{coll}"), dtype=np.int64)
        live += int((n * (n - 1) // 2).sum())
    return {
        "pair_slots": len(colls) * events * (K * (K - 1) // 2),
        "pairs_live": live,
    }


def default_fused_backend() -> str:
    """The fused evaluator a run uses when none is forced: the Pallas
    kernels on TPU, the compiled-program host interpreter elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "host"


def fused_window_skim(
    data: dict[str, np.ndarray],
    program: Program,
    store,
    payload_branches: list[str] | tuple[str, ...] = (),
    K: int | None = None,
    pad_to: int | None = None,
    backend: str | None = None,
    decision: str = "scan",
    tracer=None,
) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """One-pass skim of a decoded window (the engine's fused path).

    Evaluates the compiled predicate AND compacts the survivor payload in
    a single pass over the window, on the best executor for the backend:

      * ``"pallas"`` — the fused VMEM kernel (``kernels.skim_fused``):
        pad the window once, then predicate + one-hot MXU compaction per
        event tile.  Default on TPU.
      * ``"xla"``    — the kernel's jitted jnp oracle over the same
        padded layout (validation / non-TPU accelerators).
      * ``"host"``   — the compiled-program interpreter over the native
        jagged layout (:func:`program_eval_np`); skips densification,
        which is what makes ``fused=True`` fast on plain CPUs.  Default
        off-TPU.

    All three produce bit-identical survivor sets on the repo fixtures
    (pinned by tests/test_pipeline_executor.py).  Returns the boolean
    survivor mask and the compacted payload columns (survivor-only, event
    order).

    ``pad_to`` fixes the padded event-axis shape (e.g. to the engine's
    window size) so every window of a skim hits the same compiled kernel.
    Padding events get index >= n_events in the payload index column and
    are dropped after compaction, so a predicate that happens to accept
    an all-zero event (e.g. ``HT < x``) cannot leak phantom survivors.

    ``decision`` is the window's zone-map classification (DESIGN.md §9):
    ``"accept_all"`` skips predicate evaluation entirely — every event
    provably survives, so the payload columns pass through whole (payload
    branches are flat float32 by the planner's contract, hence identical
    to ``arr[all-true mask]``).  ``"scan"`` (default) runs the normal
    fused evaluation.  Pruned windows never reach this function: their
    data is never fetched, let alone decoded.

    ``tracer`` records the device backends' host–device boundary: the
    densification (``stage_inputs``), the uploads and kernel enqueue
    (``device_launch``, with :func:`pair_work`'s counts for pair groups)
    and the one blocking read-back of the compacted rows and their count
    (``device_wait``).
    """
    flat = next(
        n for n in data if not (store.branches.get(n) and store.branches[n].jagged)
    )
    E = len(data[flat])

    if decision == "accept_all":
        mask = np.ones(E, dtype=bool)
        return mask, {n: np.asarray(data[n]) for n in payload_branches}

    from repro.kernels import ops

    if backend is None:
        backend = default_fused_backend()

    if backend == "host":
        mask = program_eval_np(data, program, E)
        cols = {
            name: np.asarray(data[name])[mask] for name in payload_branches
        }
        return mask, cols
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown fused backend {backend!r}")

    tr = tracer if tracer is not None else NULL_TRACER
    if K is None:
        K = window_pad_K(data, program, store)
    with tr.span("stage_inputs", kind="stage_inputs") as sp:
        pb = build_padded_inputs(
            data, program, store, K=K,
            payload_branches=list(payload_branches), include_index=True,
            to_device=False,
        )
        if tr.enabled:
            sp["events"] = E
            sp["K"] = K
    target = -(-max(E, pad_to or E) // _WINDOW_QUANTUM) * _WINDOW_QUANTUM
    host = (pb.terms, pb.valid, pb.weights, pb.payload)
    with tr.span("device_launch", kind="device_launch") as sp:
        terms, valid, weights, payload = (jnp.asarray(a) for a in host)
        if target > E:
            pad = target - E
            terms = jnp.pad(terms, ((0, 0), (0, pad), (0, 0)))
            valid = jnp.pad(valid, ((0, 0), (0, pad), (0, 0)))
            weights = jnp.pad(weights, ((0, 0), (0, pad), (0, 0)))
            payload = jnp.pad(payload, ((0, pad), (0, 0)))
            payload = payload.at[E:, 0].set(
                jnp.arange(E, target, dtype=jnp.float32)
            )
        packed, count = ops.fused_skim(
            terms, valid, weights, payload, program,
            use_pallas=(backend == "pallas"),
        )
        if tr.enabled:
            sp["op"] = "fused_skim"
            sp["h2d_bytes"] = sum(a.nbytes for a in host)
            for key, value in pair_work(program, data.__getitem__, target, K).items():
                sp[key] = value
    with tr.span("device_wait", kind="device_wait") as sp:
        # slice on the host: a device slice per survivor count would
        # compile a new program for every distinct count
        rows = np.asarray(packed)
        n = int(count)
        if tr.enabled:
            sp["op"] = "fused_skim"
            sp["d2h_bytes"] = rows.nbytes + count.nbytes
            sp["arrays"] = 2
    packed = rows[:n]
    idx = packed[:, 0].astype(np.int64)
    real = idx < E  # drop phantom survivors from event-axis padding
    packed, idx = packed[real], idx[real]
    mask = np.zeros(E, dtype=bool)
    mask[idx] = True
    cols = {
        name: packed[:, 1 + j].astype(
            store.branches[name].np_dtype() if name in store.branches else np.float32
        )
        for j, name in enumerate(payload_branches)
    }
    return mask, cols


def sharded_skim(mesh, program: Program, data_axes=("pod", "data")):
    """Build the sharded near-data skim step.

    Returns a jitted fn: (terms, valid, weights, payload) sharded over the
    event axis -> (packed survivors per shard, global survivor count).
    The compaction happens *inside* the shard — only packed survivors and a
    scalar count are exposed to cross-shard collectives, which is the
    paper's "return only the filtered data" on the mesh.
    """
    axes = tuple(a for a in data_axes if a in mesh.axis_names)

    def _local(terms, valid, weights, payload):
        mask = kref.predicate_eval_ref(terms, valid, weights, program)
        packed, count = kref.stream_compact_ref(payload, mask)
        total = jax.lax.psum(count, axes)
        return packed, mask.astype(jnp.int32), total

    spec_e1 = P(None, axes, None)  # (T/G, E, K)
    spec_pay = P(axes, None)  # (E, D)

    return jax.jit(
        jax.shard_map(
            _local,
            mesh=mesh,
            in_specs=(spec_e1, spec_e1, spec_e1, spec_pay),
            out_specs=(spec_pay, P(axes), P()),
            check_vma=False,
        )
    )


__all__ = [
    "PaddedBatch",
    "Program",
    "compile_query",
    "build_padded_inputs",
    "skim_mask",
    "compact_jnp",
    "program_eval_np",
    "fused_window_skim",
    "default_fused_backend",
    "window_pad_K",
    "pair_work",
    "sharded_skim",
]
