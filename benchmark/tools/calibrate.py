#!/usr/bin/env python3
"""Read, in one process, what a cell's limits are set from.

    python3 benchmark/tools/calibrate.py --workload <cell> \
        --seeds 101,102,... [--control-seeds 101,102,103] [--rehearse]

The cell's runner does the reading (its ``calibrate``): for every seed
what the timed path produces, compared with the plain reference as a run
compares it, and for the control seeds the reference in float8 (the
control of ``benchmark/reference.py``) compared the same way. Prints each
number per seed, then the largest of the sound readings and the smallest
of the control's. The result goes into ``benchmark/limits/<cell>.json``
by hand, with the readings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default="")
    args = parser.parse_args()
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") != HERE]
    from benchmark import harness, run as run_mod

    _, cell, published, config, mix, _ = harness.load_cell(
        args.workload, args.rehearse)
    harness.say(f"compile cache: {harness.configure_cache()}")
    devices = harness.require_devices(cell["chips"], args.rehearse)
    runner = run_mod.load_module("runners", mix["runner"])

    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    sound, control, raw = runner.calibrate(config, published, mix, devices,
                                           seeds, control_seeds)
    summary = {
        "sound_largest": {k: max(r[k] for r in sound) for k in sound[0]}
        if sound else {},
        "control_smallest": {k: min(r[k] for r in control)
                             for k in control[0]} if control else {}}
    harness.say("calibration: " + json.dumps(summary))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"sound": sound, "control": control, "raw": raw,
                       **summary}, f)


if __name__ == "__main__":
    main()
