#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: serve its mix at a
list of rates, one process, and print one JSON line per rate.

    python3 bench/sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 30

Where the mix primes the engine, the primes scale with the rate (the
load a server carries grows with it). Each line gives the end-to-end
readings at that rate, the slots holding a request in the window's
first and last quarter, the requests left waiting for a slot when the
window closed, and the share of the window's requests whose first token
came within the window. Where the slots fill and the backlog grows
through the window the rate is past the knee. A cell's
rate is then fixed in its mix file; runs never search for one.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests/s, comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    if not harness.load_program():
        return 2
    from bench import driver, model, traffic

    paths = harness.Paths()
    bench = json.loads(paths.benchmark.read_text())
    cell = harness.cell_entry(bench, args.workload)
    spec = model.load_config(cell["config"], paths.configs)
    base = traffic.load(cell["traffic"], paths.traffic)
    if base.loop != "open":
        harness.say("bench/sweep.py: only an open loop has a rate to sweep")
        return 2
    devs = harness.find_chips(cell, require_tpu=True)
    if devs is None:
        return 1
    harness.enable_compile_cache(paths.cache)
    for rate in (float(r) for r in args.rates.split(",")):
        params = dict(base.params, rate_rps=rate)
        if "prime" in params:
            n = round(params["prime"]["requests"] * rate / base.params["rate_rps"])
            params["prime"] = dict(params["prime"], requests=n)
        mix = dataclasses.replace(base, params=params)
        got = harness.serve(spec, mix, args.seed, args.seconds, False, devs,
                            time.perf_counter(), paths)
        win = got.window
        inside = [r for r in got.records if r.in_window]
        waiting = [r for r in inside if not r.admitted or r.admitted > win.t1]
        on_time = [r for r in inside if r.stamps and r.stamps[0] < win.t1]
        ttft = driver.ttfts(got.records, win)
        slots = [n for n, _ in win.load]
        quarter = max(1, len(slots) // 4)
        gaps = driver.token_gaps(got.records, win.t0, win.t1)
        print(json.dumps({
            "rate_rps": rate, "requests": len(inside),
            "ttft_p50_s": driver.percentile(ttft, 50),
            "ttft_p90_s": driver.percentile(ttft, 90),
            "itl_p95_ms": 1e3 * driver.percentile(gaps, 95),
            "output_tok_s": driver.tokens_in(got.records, win.t0, win.t1)
            / args.seconds,
            "slots_first_quarter": sum(slots[:quarter]) / quarter,
            "slots_last_quarter": sum(slots[-quarter:]) / quarter,
            "waiting_at_close": len(waiting),
            "first_token_in_window": len(on_time) / max(1, len(inside)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
