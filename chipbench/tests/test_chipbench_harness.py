"""The harness end to end on the CPU at a tiny scale, with the look for a
chip skipped: sound runs come out correct; the bfloat16 control, an
altered answer and half the rows left out come out not correct; cells,
traffic mixes and metrics added as files are found by name; and the
command itself refuses to run without a TPU that ``peaks.json`` lists."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import control, harness, spec  # noqa: E402

CELLS = ["tc.kron-s16.tile", "tc.urand-s16.auto"]
#: tiny stand-ins for the cells' scale 16
SCALE = 8
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def small_root(tmp_path: pathlib.Path, scale: int = SCALE) -> pathlib.Path:
    """A checkout whose configurations are cut to ``scale``."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for path in (tmp_path / "chipbench" / "configs").glob("*.json"):
        config = json.loads(path.read_text())
        config["graph"]["scale"] = scale
        path.write_text(json.dumps(config))
    return tmp_path


def run(tmp_path, cell, trace=False, seed=2 ** 31 + 3):
    return harness.execute(spec.load_cell(cell, root=small_root(tmp_path)),
                           seed, 0.0, trace, dict(CPU), 0.0)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, cell):
    res = run(tmp_path, cell)
    assert res["correct"] is True
    assert (res["attempted"], res["failed"]) == (1, 0)
    assert set(res["metrics"]) == {"setup_s", "solve_s"}   # no HBM here
    assert list(res)[-1] == "checks"
    assert res["checks"] == {k: {"value": 0.0, "limit": 0}
                             for k in ("count_gap", "edge_gap")}


def test_traced_run_reads_the_layers_it_can(tmp_path):
    """On the CPU there is no device plane: the device metrics are left
    out, the program's spans and counters are read."""
    from repro.core import planner
    planner.clear_plan_cache()
    res = run(tmp_path, "tc.urand-s16.auto", trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"compiles_in_window", "host_prep_s",
                                   "plan_s"}
    assert res["metrics"]["compiles_in_window"]["value"] == 0.0
    assert res["metrics"]["plan_s"]["value"] > 0


def _alter_one_answer(real):
    def faulty(*args, **kwargs):
        res = real(*args, **kwargs)
        first = int(np.argmax(np.asarray(res.present).ravel()))
        vals = res.vals.ravel().at[first].add(1.0).reshape(res.vals.shape)
        return type(res)(vals, res.present, res.mask_cols, res.shape)
    return faulty


def _drop_half_the_rows(real):
    def faulty(*args, **kwargs):
        res = real(*args, **kwargs)
        keep = np.arange(res.shape[0])[:, None] < res.shape[0] // 2
        return type(res)(np.where(keep, res.vals, 0),
                         np.where(keep, res.present, False),
                         res.mask_cols, res.shape)
    return faulty


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_alter_one_answer, _drop_half_the_rows])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, cell,
                                          fault):
    import repro.core
    monkeypatch.setattr(repro.core, "masked_spgemm",
                        fault(repro.core.masked_spgemm))
    res = run(tmp_path, cell)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] == 1
    assert res["checks"]["edge_gap"]["value"] > 0
    assert res["checks"]["count_gap"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(tmp_path, cell):
    """The reference in bfloat16, in the program's place, fails the
    count on every seed tried (at scale 10: thousands of triangles)."""
    c = spec.load_cell(cell, root=small_root(tmp_path, scale=10))
    for seed in (1, 2, 2 ** 31 + 5):
        got = control.readings(c, seed)
        assert got["count_gap"] > harness.LIMITS["count_gap"]


def test_added_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a metric added as files, with
    entries in BENCHMARK.json, run without an edit to any other file."""
    root = small_root(tmp_path)
    bench_dir = root / "chipbench"
    (bench_dir / "configs" / "tiny-ring.json").write_text(json.dumps({
        "graph": {"generator": "kronecker", "scale": 7, "edge_factor": 8,
                  "a": 0.45, "b": 0.15, "c": 0.15, "graph_seed": 3}}))
    (bench_dir / "traffic" / "closed-tile-bs16.json").write_text(
        json.dumps({"loop": "closed", "clients": 1,
                    "masked_spgemm": {"algorithm": "tile",
                                      "tile_block": 16}}))
    (bench_dir / "metrics" / "tile_spans.py").write_text(
        "def read(r):\n"
        "    n = sum(s['name'] == 'spgemm.tile' for s in r.spans)\n"
        "    return n / r.solves if n else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-ring", "source": "a test",
                             "file": "chipbench/configs/tiny-ring.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tc.tiny.tile16",
                               "config": "tiny-ring",
                               "traffic": "closed-tile-bs16", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "tile_spans", "unit": "count",
                               "better": "lower", "source": "program_span",
                               "layer": "tile executor", "moves": "solve_s",
                               "workloads": ["tc.tiny.tile16"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("tc.tiny.tile16", root=root)
    assert cell.traffic["masked_spgemm"]["tile_block"] == 16
    res = harness.execute(cell, 9, 0.0, True, dict(CPU), 0.0)
    assert res["correct"] is True
    assert res["metrics"]["tile_spans"] == {"value": 1.0, "unit": "count"}
    assert "tile_spans" not in run(tmp_path / "b", CELLS[0], True)["metrics"]


def test_open_loop_traffic_is_refused(tmp_path):
    root = small_root(tmp_path)
    path = root / "chipbench" / "traffic" / "closed-auto.json"
    path.write_text(json.dumps({"loop": "open", "clients": 1,
                                "masked_spgemm": {}}))
    with pytest.raises(spec.SpecError):
        spec.load_cell("tc.urand-s16.auto", root=root)


def test_command_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


class _FakeChip:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,chips,ok", [
    ("TPU v5 lite", 1, True), ("TPU v99", 1, False),
    ("TPU v5 lite", 4, False)])
def test_device_check(monkeypatch, capsys, kind, chips, ok):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_FakeChip(kind)])
    peaks = spec.load_peaks()
    if ok:
        assert harness.check_device(chips, peaks)["kind"] == kind
        return
    with pytest.raises(harness.DeviceError):
        harness.check_device(chips, peaks)
    if chips == 1:
        assert harness.main(["--workload", CELLS[0], "--seed", "1",
                             "--seconds", "1"], 0.0) == 2
        assert capsys.readouterr().out == ""
