"""chip_smoke.py on the CPU: the device check refuses to go on, and the
phases behind it (store, service drain, host reference, bitwise
comparison, compaction check) run end to end at a tiny size."""

import io
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_device_check_exits_nonzero_without_tpu():
    out = io.StringIO()
    with redirect_stdout(out), pytest.raises(SystemExit) as exc:
        chip_smoke.main(["--events", "1000"])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in out.getvalue()


@pytest.fixture(scope="module")
def smoke_runs():
    n, seed = 3000, chip_smoke.SEED
    qs = chip_smoke.queries()
    store = chip_smoke.build_store(n, seed, decode_backend="device")
    runs = {b: chip_smoke.serve(store, qs, b) for b in chip_smoke.DEVICE_BATCHES}
    return store, runs, chip_smoke.host_reference((n, seed), qs)


@pytest.mark.parametrize("device_batch", chip_smoke.DEVICE_BATCHES)
def test_smoke_jobs_match_host_reference(smoke_runs, device_batch):
    _store, runs, ref = smoke_runs
    for name, (job, _wall, _comp) in runs[device_batch].items():
        assert job.state == "DONE", job.error
        assert chip_smoke.output_mismatches(job.result, ref[name]) == []
    assert any(r.n_passed for r in ref.values())


def test_smoke_decode_ran_on_device_tier(smoke_runs):
    stats = smoke_runs[0].decode_backend_stats()
    assert stats["backend"] == "device"
    assert stats["device_baskets"] > 0 and stats["fallbacks"] == 0


def test_smoke_compaction_check_is_bit_exact():
    assert chip_smoke.compaction_mismatches(chip_smoke.SEED) == []
