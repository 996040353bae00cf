"""What the reference and the generator read for ``nanoaod_skim_node``,
pinned: a digest of every generated column, of every selection node's
mask and margin under float64 and under bfloat16 for each query
template, and of each template's output branches.  The digests were
recorded before the node rules moved into ``rules/<type>.py`` and the
generator learnt declared schemas; neither move may change a bit."""

import hashlib
import json
import os

import ml_dtypes
import numpy as np
import pytest

from harness import spec
from harness.gen import nanoaod_columns
from harness.reference import Columns, Selection, output_branches

N_EVENTS = 20_000
SEEDS = (1, 2**31 - 1, 2**31 + 12_345)
TEMPLATES = ("higgs", "slim", "zee_mass", "e_jet_dr")
DTYPES = {"float64": np.float64, "bfloat16": ml_dtypes.bfloat16}


def _hex(*parts: bytes) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p)
    return h.hexdigest()[:16]


def _array(a: np.ndarray | None) -> bytes:
    if a is None:
        return b"none"
    a = np.ascontiguousarray(a)
    return a.dtype.str.encode() + str(a.shape).encode() + a.tobytes()


def _columns(seed: int) -> Columns:
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "nanoaod_skim_node")
    store = json.load(open(os.path.join(spec.ROOT, entry["file"])))["store"]
    store = {**store, "n_events": N_EVENTS}
    return Columns(*nanoaod_columns(store, seed))


def _template(name: str) -> dict:
    return json.load(open(os.path.join(spec.BENCH_DIR, "templates", f"{name}.json")))


def digests(seed: int) -> dict:
    cols = _columns(seed)
    out = {
        "columns": _hex(*(n.encode() + _array(c) for n, c in cols.columns.items())),
        "jagged": _hex(json.dumps(cols.jagged).encode()),
    }
    for t in TEMPLATES:
        doc = _template(t)
        out[f"{t}.branches"] = _hex(json.dumps(output_branches(doc, cols)).encode())
        for dname, dtype in DTYPES.items():
            sel = Selection(cols, dtype=dtype)
            out[f"{t}.{dname}"] = [_hex(_array(m), _array(g)) for m, g in sel.nodes(doc)]
    return out


GOLDEN = {
    1: {
        "columns": "14b2df9fa84f4862",
        "jagged": "2cd53c9ec0ecc386",
        "higgs.branches": "1361f4eec9b19b05",
        "higgs.float64": [
            "35ffbcef84324512", "bb8db10474ca90c4", "f082688d380a767a", "2d281c1552ada3ea",
            "058049ca26c380ab", "93c7fe48dfeb8aae",
        ],
        "higgs.bfloat16": [
            "35ffbcef84324512", "bb8db10474ca90c4", "f082688d380a767a", "201f69dccfb25064",
            "058049ca26c380ab", "93c7fe48dfeb8aae",
        ],
        "slim.branches": "1361f4eec9b19b05",
        "slim.float64": ["9cca6acb6ef02fa7"],
        "slim.bfloat16": ["9cca6acb6ef02fa7"],
        "zee_mass.branches": "8704036284ef7d1e",
        "zee_mass.float64": ["2e901b7dd25d30c6"],
        "zee_mass.bfloat16": ["2d269df5e447ed6a"],
        "e_jet_dr.branches": "7fb3cb8c7942f27f",
        "e_jet_dr.float64": ["9394ec0185ae44e4", "50dbe9c00f068fd5"],
        "e_jet_dr.bfloat16": ["9394ec0185ae44e4", "618ea02dfdf9660b"],
    },
    2**31 - 1: {
        "columns": "45238d69a90e2dad",
        "jagged": "2cd53c9ec0ecc386",
        "higgs.branches": "1361f4eec9b19b05",
        "higgs.float64": [
            "7a937e324288a8e9", "65ac5169cd598449", "f2d4379c16576af0", "092404d843915799",
            "4258455a1b690499", "804e27eeb5faa451",
        ],
        "higgs.bfloat16": [
            "7a937e324288a8e9", "65ac5169cd598449", "f2d4379c16576af0", "7554fb171237d9ae",
            "4258455a1b690499", "804e27eeb5faa451",
        ],
        "slim.branches": "1361f4eec9b19b05",
        "slim.float64": ["9cca6acb6ef02fa7"],
        "slim.bfloat16": ["9cca6acb6ef02fa7"],
        "zee_mass.branches": "8704036284ef7d1e",
        "zee_mass.float64": ["53d49fd431d1d6a4"],
        "zee_mass.bfloat16": ["1dbea15371d5c129"],
        "e_jet_dr.branches": "7fb3cb8c7942f27f",
        "e_jet_dr.float64": ["b91ed20fe333ab5d", "24dd08aac0cb5f1b"],
        "e_jet_dr.bfloat16": ["b91ed20fe333ab5d", "d459a94b337447d2"],
    },
    2**31 + 12_345: {
        "columns": "8e85e49fc43c724e",
        "jagged": "2cd53c9ec0ecc386",
        "higgs.branches": "1361f4eec9b19b05",
        "higgs.float64": [
            "83d596b64b948d4e", "118af9db86b72d25", "ab8475a9d6d0a7bb", "c68820b22047f37e",
            "791da1eda919e262", "8fa21fa9d41ea52b",
        ],
        "higgs.bfloat16": [
            "83d596b64b948d4e", "118af9db86b72d25", "ab8475a9d6d0a7bb", "f6f27781d7e9d1c7",
            "791da1eda919e262", "8fa21fa9d41ea52b",
        ],
        "slim.branches": "1361f4eec9b19b05",
        "slim.float64": ["9cca6acb6ef02fa7"],
        "slim.bfloat16": ["9cca6acb6ef02fa7"],
        "zee_mass.branches": "8704036284ef7d1e",
        "zee_mass.float64": ["91f85b30fe919dd9"],
        "zee_mass.bfloat16": ["87a5379b34065c40"],
        "e_jet_dr.branches": "7fb3cb8c7942f27f",
        "e_jet_dr.float64": ["d22bde6ab08970c9", "82536f969e44c8a8"],
        "e_jet_dr.bfloat16": ["d22bde6ab08970c9", "cc96152f28c777a4"],
    },
}


@pytest.mark.parametrize("seed", SEEDS)
def test_digests_as_recorded(seed):
    assert digests(seed) == GOLDEN[seed]
