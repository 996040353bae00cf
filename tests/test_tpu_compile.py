"""The main-path kernels compile for a TPU v5e at the chip smoke's widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which raises what the chip's compiler
would raise -- tiling rules, unlowerable primitives, VMEM overflow.
Shapes are those ``chip_smoke.py`` drives: 4096-event windows (one
default basket), batches of 4 windows, object capacity K=16 for
programs reading jets and K=8 for electron-only ones, 4096-value
baskets with up to 32 bit-planes.  The ADL Query 5 all-pairs group
compiles at both capacities its windows take, K=8 and K=16.

The topology is described inside a fixture, never at import time: only
the worker that runs this file loads the TPU compiler.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.core.query import parse_query  # noqa: E402
from repro.kernels import basket_decode as bd  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels import predicate_eval as pe  # noqa: E402
from repro.kernels import skim_fused as sf  # noqa: E402

E, BATCH, D, WORDS = 4096, 4, 8, 128
# (query name in chip_smoke.queries(), object capacity K)
PROGRAMS = [("higgs", 16), ("zee_mass", 8), ("e_jet_dr", 16), ("adl_q5", 8), ("adl_q5", 16)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _program(name):
    q = dict(chip_smoke.queries()[name], branches=["MET_pt"])
    return pe.compile_query(parse_query(q))


def _assert_kernel(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,K", PROGRAMS)
def test_skim_fused_compiles(shape, name, K):
    p = _program(name)
    T, G = p.n_terms, p.n_groups
    _assert_kernel(
        lambda t, v, w, x: sf.skim_fused(t, v, w, x, program=p, interpret=False),
        shape((T, E, K)), shape((G, E, K)), shape((G, E, K)), shape((E, D)),
    )


@pytest.mark.parametrize("name,K", PROGRAMS)
def test_skim_fused_batch_compiles(shape, name, K):
    p = _program(name)
    T, G = p.n_terms, p.n_groups
    _assert_kernel(
        lambda t, v, w, x: sf.skim_fused_batch(t, v, w, x, program=p, interpret=False),
        shape((BATCH, T, E, K)), shape((BATCH, G, E, K)),
        shape((BATCH, G, E, K)), shape((BATCH, E, D)),
    )


@pytest.mark.parametrize("name,K", PROGRAMS)
def test_predicate_eval_compiles(shape, name, K):
    p = _program(name)
    T, G = p.n_terms, p.n_groups
    _assert_kernel(
        lambda t, v, w: pe.predicate_eval(t, v, w, program=p, interpret=False),
        shape((T, E, K)), shape((G, E, K)), shape((G, E, K)),
    )


@pytest.mark.parametrize("name,K", PROGRAMS)
def test_predicate_eval_batch_compiles(shape, name, K):
    p = _program(name)
    T, G = p.n_terms, p.n_groups
    _assert_kernel(
        lambda t, v, w: pe.predicate_eval_batch(t, v, w, program=p, interpret=False),
        shape((BATCH, T, E, K)), shape((BATCH, G, E, K)), shape((BATCH, G, E, K)),
    )


@pytest.mark.parametrize("kind", [bd.KIND_INT, bd.KIND_FLOAT, bd.KIND_BOOL])
@pytest.mark.parametrize("n_bits", [8, 32])
# one basket, and the grouped launches of a decode round: flat baskets,
# and a jagged branch's wider ones
@pytest.mark.parametrize("n_baskets,words", [(1, WORDS), (28, WORDS), (7, 640)])
def test_basket_decode_compiles(shape, kind, n_bits, n_baskets, words):
    _assert_kernel(
        lambda planes, firsts: bd.basket_decode(
            planes, firsts, kind=kind, n_bits=n_bits, interpret=False
        ),
        shape((n_baskets, n_bits, words), jnp.uint32), shape((n_baskets,), jnp.uint32),
    )


@pytest.mark.parametrize("name,K", PROGRAMS)
def test_cascade_stage_step_compiles(shape, name, K):
    p = _program(name)
    T, G = p.n_terms, p.n_groups
    nb = E // 4096 + 2
    compiled = ops._cascade_stage_jit.lower(
        shape((BATCH, T, E, K)), shape((BATCH, G, E, K)), shape((BATCH, G, E, K)),
        shape((BATCH, E // 32), jnp.uint32), shape((BATCH, E), jnp.int32),
        program=p, nb=nb, use_pallas=True, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
