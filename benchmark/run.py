#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``benchmark/configs/``) and a traffic mix
(``benchmark/traffic/``); the mix names its runner
(``benchmark/runners/<runner>.py``); each metric is a reader of its own
(``benchmark/e2e_metrics/<name>.py``, ``benchmark/layer_metrics/<name>.py``)
over the summary the runner returns. This file holds no configuration,
cell or metric name: a later PR adds files and entries and edits nothing.

The last line of standard output is the result, one JSON object; every
number compared for ``correct`` is printed beside its limit before it.
Without a TPU, or with fewer devices than the cell's ``chips``, the run
exits non-zero and prints no result. ``--rehearse`` (never used by the
driver) runs the same control flow at the files' toy sizes on whatever
backend JAX finds.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(directory, name):
    """The module ``benchmark/<directory>/<name>.py`` (a name may hold
    dots, so it is loaded by path)."""
    key = f"benchmark.{directory}.{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no {directory}/{name}.py")
    spec = importlib.util.spec_from_file_location(key, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[key] = module
    spec.loader.exec_module(module)
    return module


def applies(metric, cell):
    return cell in metric.get("workloads", [cell])


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        raise SystemExit("benchmark: no horovod_tpu/ beside benchmark/: "
                         "there is no program here to measure")
    # the checkout, not this directory, is the import root: modules here
    # are ``benchmark.<name>`` and shadow nothing of the standard library
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") != HERE]
    from benchmark import harness

    spec, cell, published, config, mix, limits = harness.load_cell(
        args.workload, args.rehearse)

    harness.say(f"compile cache: {harness.configure_cache()}")
    devices = harness.require_devices(cell["chips"], args.rehearse)
    harness.say(f"devices: {devices} seed {args.seed}")
    context = harness.Context(
        config=config, published=published, mix=mix, limits=limits,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, started=STARTED,
        compiles=harness.CompileCounter())

    summary = load_module("runners", mix["runner"]).run(context)

    kind, directory = (("per_layer", "layer_metrics") if args.trace
                       else ("end_to_end", "e2e_metrics"))
    metrics = {}
    for metric in spec[kind]:
        if not applies(metric, cell["name"]):
            continue
        value = load_module(directory, metric["name"]).read(summary)
        if value is None:
            if kind == "end_to_end":
                raise SystemExit(f"benchmark: {metric['name']} has nothing "
                                 f"to read in {cell['name']}")
            continue
        metrics[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}

    for check in summary["checks"]:
        harness.say(check.line())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": summary["memory_peak_bytes"]}
    result = {
        "correct": bool(summary["failed"] == 0
                        and all(c.ok for c in summary["checks"])),
        "attempted": int(summary["attempted"]),
        "failed": int(summary["failed"]),
        "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = summary["trace"]["busy_s"]
        device["window_s"] = summary["trace"]["window_s"]
        result["breakdown"] = summary["breakdown"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
