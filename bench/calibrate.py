#!/usr/bin/env python3
"""Readings that a cell's limit for ``correct`` is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 48

For each seed, in one process: serve the cell as a run does (same
traffic, same window, same drain), then compare the same sample of
served requests twice with the float32 reference: once for the tokens
the program served (the readings a run compares, ``logit_gap`` and
``mean_gap``) and once for the tokens a float8 reference would have
chosen at the same positions (``control.*``: the control, the reference
one precision below the bf16 the configurations serve in). One JSON line
per seed, then for each number the largest program reading and the
smallest control reading. The benchmark's own runs never run the
control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if not harness.load_program():
        return 2
    from bench import model, traffic

    paths = harness.Paths()
    bench = json.loads(paths.benchmark.read_text())
    cell = harness.cell_entry(bench, args.workload)
    spec = model.load_config(cell["config"], paths.configs)
    mix = traffic.load(cell["traffic"], paths.traffic)
    limits = harness.load_limits(paths, cell["name"])
    devs = harness.find_chips(cell, require_tpu=True)
    if devs is None:
        return 1
    harness.say(f"compile cache: {harness.enable_compile_cache(paths.cache)}")
    every = {"logit_gap": 1e9, "mean_gap": 1e9}  # read both numbers
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        got = harness.serve(spec, mix, seed, args.seconds, False, devs,
                            time.perf_counter(), paths)
        checks = harness.check_outputs(got.records, spec, seed, every,
                                       control=True)
        rows.append({k: v["value"] for k, v in checks.items()})
        print(json.dumps({"seed": seed, **rows[-1]}), flush=True)
    summary = {"workload": cell["name"], "limits now": limits,
               "seconds": time.perf_counter() - T_START}
    for name in every:
        prog = [r[name] for r in rows if r[name] is not None]
        low = [r[f"control.{name}"] for r in rows
               if r.get(f"control.{name}") is not None]
        summary[name] = {"program_max": max(prog, default=None),
                         "control_min": min(low, default=None)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
