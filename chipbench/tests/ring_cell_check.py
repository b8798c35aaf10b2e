"""Subprocess body of ``test_chipbench_ring.py``: the four-chip ring cell
end to end on 4 forced host devices, at scale 8, with the look for a chip
skipped.  Prints one JSON line per run: sound, then with one answer
altered."""
import json
import os
import pathlib
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, spec  # noqa: E402
from chipbench.tests.test_chipbench_harness import (  # noqa: E402
    _alter_one_answer, small_root)

CELL = "tc.kron-s17.ring"
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 4}


def main():
    import repro.core
    cell = spec.load_cell(CELL, root=small_root(pathlib.Path(
        tempfile.mkdtemp(prefix="ring-cell-"))))
    for traced in (False, True):
        res = harness.execute(cell, 2 ** 31 + 3, 0.0, traced, dict(CPU), 0.0)
        print(json.dumps(res), flush=True)
    repro.core.masked_spgemm = _alter_one_answer(repro.core.masked_spgemm)
    print(json.dumps(harness.execute(cell, 7, 0.0, False, dict(CPU), 0.0)),
          flush=True)


if __name__ == "__main__":
    main()
