"""The control of the comparison that decides ``correct``.

The control is the plain reference put in the program's place and computed
in bfloat16, the nearest precision below the configurations' float32
(``reference.bf16_control``).  The comparison has to find it wrong, or it
could not find a later change to a lower precision wrong either.  The
benchmark's own runs never run it.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3

builds each seed's input as a run of the cell does, at the cell's own
size, and prints each number compared for the control beside its limit.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import graphs, harness, reference, spec


def control_output(indptr, indices) -> harness.Output:
    """What a solve would return if the bfloat16 reference computed it."""
    ref = reference.triangles(indptr, indices)
    values, present, count = reference.bf16_control(ref)
    return harness.Output(ref.indptr, ref.indices, values, present,
                          ref.indices.copy(), count)


def readings(cell: spec.Cell, seed: int) -> dict:
    """The numbers compared for the control on one seed's input."""
    indptr, indices = graphs.permute(
        *graphs.generate(cell.config["graph"]), seed)
    checked = harness.compare([control_output(indptr, indices)],
                              reference.triangles(indptr, indices))
    return checked["worst"]


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        got = readings(cell, seed)
        fails = [k for k, v in got.items() if v > harness.LIMITS[k]]
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": got, "limits": harness.LIMITS,
                          "fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
