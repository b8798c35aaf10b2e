"""``masked_spgemm(..., devices=n)``: the sparse ring through the normal
entry point.

The multi-device checks run in a child interpreter with 4 forced host
devices (``tests/ring_check.py``): the main pytest process must keep
seeing 1 device.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import masked_spgemm
from repro.core.formats import tril

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("check", ["exact_counts",
                                   "every_device_holds_blocks",
                                   "indivisible_block_rows",
                                   "stored_zeros_and_cancellation"])
def test_ring_on_four_devices(check):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "ring_check.py"), check],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "RING_CHECK_OK" in proc.stdout


def _lower():
    from repro.core.formats import erdos_renyi
    return tril(erdos_renyi(96, 8, seed=5, values="ones"))


@pytest.mark.parametrize("options", [{"algorithm": "auto"},
                                     {"algorithm": "tile", "tile_block": 8},
                                     {"algorithm": "msa"}])
def test_one_device_is_the_single_device_path(options):
    L = _lower()
    want = masked_spgemm(L, L, L, **options)
    got = masked_spgemm(L, L, L, devices=1, **options)
    for field in ("vals", "present", "mask_cols"):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)))
    assert got.shape == want.shape


def test_devices_refuses_what_the_mesh_cannot_honour():
    L = _lower()
    with pytest.raises(NotImplementedError):
        masked_spgemm(L, L, L, algorithm="ring", devices=4, two_phase=True)
    with pytest.raises(ValueError, match="devices"):
        masked_spgemm(L, L, L, algorithm="ring", devices=64)
