"""The four-chip ring cell end to end on the CPU, on 4 forced host devices
in a child interpreter (the main process keeps its one device): a sound
run is correct and reports the program's spans, an altered answer is
not correct, and the bfloat16 control is not correct."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import control, harness, spec  # noqa: E402
from chipbench.tests.test_chipbench_harness import small_root  # noqa: E402


def test_ring_cell_runs_and_checks_on_four_devices():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(pathlib.Path(__file__).with_name(
            "ring_cell_check.py"))],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    sound, traced, altered = [json.loads(line) for line in
                              proc.stdout.splitlines()
                              if line.startswith("{")]
    for res in (sound, traced):
        assert res["correct"] is True and res["failed"] == 0
        assert res["checks"] == {k: {"value": 0.0, "limit": 0}
                                 for k in harness.LIMITS}
    assert set(sound["metrics"]) == {"setup_s", "solve_s"}
    # no device plane on the CPU; the spans are read (the ring prep was
    # cached by the untraced run's set-up, so ring_prep_s has no span)
    assert set(traced["metrics"]) == {"compiles_in_window", "host_prep_s"}
    assert altered["correct"] is False
    assert altered["checks"]["edge_gap"]["value"] > 0


def test_ring_cell_bf16_control_is_not_correct(tmp_path):
    """The bfloat16 reference in the program's place fails the count (at
    scale 10: most of 75,692 triangles lost)."""
    cell = spec.load_cell("tc.kron-s17.ring",
                          root=small_root(tmp_path, scale=10))
    got = control.readings(cell, 2 ** 31 + 5)
    assert got["count_gap"] > harness.LIMITS["count_gap"]
