"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its limit for ``correct`` is
``bench/limits/<cell>.json`` and each metric is read by
``bench/metrics/<metric>.py`` (a ``read(ctx)`` returning a number, or
None where it finds nothing to read). Adding a configuration, a mix, a
cell or a metric adds files and entries; this file stays as it is.

A run: make the weights from the seed on the chip, build the program's
paged serving engine, compile its two programs ahead of time and run
each once; serve the mix's warm-up uncounted; measure for ``--seconds``
(``--trace 1``: the window's last TRACE_S seconds run under the
profiler, and the per-layer metrics are read from them); drain; read
the peak device memory; free the engine; compare a sample of the
requests served, finished or not, with the reference. ``correct`` needs
every number within its limit and no request of the window failed. The last line of
standard output is one JSON object; the numbers compared, each beside
its limit, are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_S = 10.0      # the traced span: the window's last seconds
DRAIN_S = 60.0      # longest wait for the window's first tokens
SAMPLE_TOKENS = 600  # served tokens the comparison reads, at least
SAMPLE_REQUESTS = 8  # ... from at most this many requests


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Paths:
    benchmark: pathlib.Path = ROOT / "BENCHMARK.json"
    configs: pathlib.Path = HERE / "configs"
    traffic: pathlib.Path = HERE / "traffic"
    limits: pathlib.Path = HERE / "limits"
    metrics: pathlib.Path = HERE / "metrics"
    cache: pathlib.Path = ROOT / ".jax_cache"
    scratch: pathlib.Path = ROOT / ".bench_run"


@dataclasses.dataclass
class Context:
    """What a metric reader sees (see bench/metrics/)."""

    spec: dict                    # the configuration file
    records: list                 # bench.driver.Record, every request
    window: object                # bench.driver.Window
    window_s: float
    setup_s: float
    peaks: Optional[dict] = None  # bench.peaks entry of this chip
    trace: Optional[object] = None  # bench.trace.Reduced (--trace 1)
    calls: Dict[str, list] = dataclasses.field(default_factory=dict)
    programs: Dict[str, object] = dataclasses.field(default_factory=dict)


def load_reader(path: pathlib.Path) -> Callable:
    name = "bench_metric_" + path.stem.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def load_limits(paths: Paths, cell: str) -> Dict[str, float]:
    """The numbers the cell compares and their limits: the ``limits``
    of ``bench/limits/<cell>.json`` (the file also keeps the readings
    each limit was set from)."""
    data = json.loads((paths.limits / f"{cell}.json").read_text())
    return {k: float(v) for k, v in data["limits"].items()}


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench/run.py: no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones (a metric without ``workloads`` belongs to every cell)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def enable_compile_cache(path: pathlib.Path) -> str:
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return env or str(path)


# ---------------------------------------------------------------------------


class Calls:
    """Wraps the engine's two programs: a host span around each call,
    and in a traced run the host's lengths of what the call works on
    (``decode``: the active slots' cached lengths; ``prefill``: the
    chunk's start and valid tokens)."""

    def __init__(self):
        self.log: Dict[str, list] = {"decode": [], "prefill": []}
        self.recording = False  # set while a traced window is open

    def wrap(self, kind: str, fn):
        from jax.profiler import TraceAnnotation

        span = f"bench.{kind}_call"

        def call(*a):
            with TraceAnnotation(span):
                out = fn(*a)
            if self.recording:
                if kind == "decode":
                    active = np.asarray(a[4])
                    self.log[kind].append(np.asarray(a[3])[active].tolist())
                else:
                    self.log[kind].append((int(np.asarray(a[3])[0]),
                                           int(np.asarray(a[4])[0])))
            return out

        return call


def compile_programs(eng, programs, calls: Optional[Calls]):
    """Compile both programs at the engine's shapes, run each once on
    idle inputs (every write goes to the scratch page), and hand the
    compiled programs to the engine. Returns (compile seconds, HLO text)
    by program."""
    import jax
    import jax.numpy as jnp

    out, hlo = {}, {}
    compiled = {}
    for kind, (fn, host_args) in programs.items():
        args = (eng.params, eng.pools) + tuple(jnp.asarray(x) for x in host_args)
        t0 = time.perf_counter()
        c = fn.lower(*args).compile()
        out[kind] = time.perf_counter() - t0
        m = c.memory_analysis()
        if m is not None:
            say(f"program {kind}: compile {out[kind]:.3f} s, arguments "
                f"{m.argument_size_in_bytes} B, temporaries "
                f"{m.temp_size_in_bytes} B, outputs {m.output_size_in_bytes} B")
        logits, eng.pools = c(*args)
        jax.block_until_ready((logits, eng.pools))
        compiled[kind] = c
        hlo[kind] = c.as_text()
    eng.decode_fn = compiled["decode"]
    eng.prefill_fn = compiled["prefill"]
    if calls is not None:
        eng.decode_fn = calls.wrap("decode", eng.decode_fn)
        eng.prefill_fn = calls.wrap("prefill", eng.prefill_fn)
    return out, hlo


def make_request(r):
    """The engine's greedy request for a generated one."""
    from repro.serve import Request

    return Request(prompt=r.prompt, max_new_tokens=r.max_new, temperature=0.0)


def check_outputs(records, spec: dict, seed: int, limits: Dict[str, float],
                  *, control: bool = False) -> Dict[str, dict]:
    """Compare a sample of the requests that were served tokens, finished
    or still in flight, with the reference (run after the engine is
    freed). Returns {name: {value, limit}} for the
    numbers ``limits`` names and the served tokens compared; the other
    readings are printed. With ``control``, also the readings of the
    tokens the float8 reference would have chosen at the same positions
    (``control.<name>``, limit as the number's)."""
    from bench import model
    from bench.reference import compare
    from bench.reference import model as ref

    rng = np.random.default_rng([seed, 0xC4EC])
    picked = compare.sample(comparable(records), rng, min_tokens=SAMPLE_TOKENS,
                            max_requests=SAMPLE_REQUESTS)
    out = {name: {"value": None, "limit": lim} for name, lim in limits.items()}
    out["served_tokens"] = {"value": 0, "limit": 1}
    if not picked:
        return out
    params = model.make_params(seed, spec)
    top = model.unpack_top(params, spec)

    def layer(i):
        return model.unpack_layer(params["layers"], i, spec)

    served, low = [], []
    for req in picked:
        tokens, rows, chosen = compare.positions(req)
        ref_logits = ref.logits(tokens, rows, spec, layer, top)
        served.append(compare.gaps(ref_logits, chosen))
        if control:
            f8 = ref.logits(tokens, rows, spec, layer, top, quant="fp8")
            low.append(compare.gaps(ref_logits, f8.argmax(axis=-1)))
            del f8
        del ref_logits
    read = compare.readings(served)
    n = sum(len(r.out_tokens) for r in picked)
    say(f"compared {len(picked)} requests, {n} served tokens (longest "
        f"{len(picked[0].out_tokens)}): {read}")
    for name, value in read.items():
        if name in out:
            out[name]["value"] = value
    out["served_tokens"]["value"] = n
    if control:
        for name, value in compare.readings(low).items():
            out[f"control.{name}"] = {"value": value,
                                      "limit": limits.get(name)}
    return out


def comparable(records) -> list:
    """The requests the comparison draws from: every one served a token
    and not cut short, finished or still in flight."""
    return [r.req for r in records if r.req.out_tokens and not r.req.truncated]


def passed(checks: Dict[str, dict]) -> bool:
    """Every number at or under its limit, and at least one token."""
    return checks["served_tokens"]["value"] >= checks["served_tokens"]["limit"] \
        and all(c["value"] is not None and c["value"] <= c["limit"]
                for name, c in checks.items() if name != "served_tokens")


def verdict(checks: Dict[str, dict], failed: int) -> bool:
    """``correct``: every number within its limit and no request of the
    window failed."""
    return passed(checks) and failed == 0


def device_info(devs, chips: int) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peak_bytes(devs) -> int:
    stats = devs[0].memory_stats() or {}
    if stats:
        say(f"device memory: peak {stats.get('peak_bytes_in_use')} B of "
            f"{stats.get('bytes_limit')} B")
    return int(stats.get("peak_bytes_in_use", 0))


# ---------------------------------------------------------------------------


def parse(argv=None):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_program() -> bool:
    """Put the checkout's program and the benchmark on the path; False
    when the checkout holds no program."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        say(f"bench/run.py: no program at {src}/repro; run from a checkout")
        return False
    for p in (str(src), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    return True


def find_chips(cell: dict, require_tpu: bool):
    """The devices to run on, or None (with the reason on stderr)."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        say(f"bench/run.py: JAX finds no TPU (platform {devs[0].platform}); "
            "nothing was run")
        return None
    if len(devs) < cell["chips"]:
        say(f"bench/run.py: the cell needs {cell['chips']} chips, JAX finds "
            f"{len(devs)}")
        return None
    return devs


@dataclasses.dataclass
class Served:
    """What one serving run left for the metrics and the check."""

    records: list
    window: object
    window_s: float
    setup_s: float
    calls: Dict[str, list]
    programs: Dict[str, object]
    memory_peak_bytes: int
    prof_dir: Optional[pathlib.Path] = None


def serve(spec: dict, mix, seed: int, seconds: float, traced: bool, devs,
          t_start: float, paths: Paths,
          fault: Optional[Callable] = None) -> Served:
    """Set up the engine and serve the mix: warm-up, then the window
    (under the profiler when ``traced``), then the drain. The engine is
    freed on return."""
    import jax

    from bench import driver, kernels, model, traffic

    calls = Calls()
    eng, programs = model.build_engine(spec, seed, devs[:1])
    compile_s, hlo = compile_programs(eng, programs, calls)
    progs = {k: kernels.Program(text, spec) for k, text in hlo.items()}
    del hlo, programs
    if fault is not None:
        fault(eng)
    warm, window = traffic.phases(mix, seed, seconds, spec["vocab_size"])
    prof_dir = paths.scratch / f"trace-{os.getpid()}" if traced else None
    marks = {}

    def start_trace():
        shutil.rmtree(prof_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
        marks["span"] = jax.profiler.TraceAnnotation("bench.window")
        marks["span"].__enter__()
        calls.recording = True

    def stop_trace():
        if calls.recording:
            calls.recording = False
            marks.pop("span").__exit__(None, None, None)
            jax.profiler.stop_trace()

    def on_window(opening: bool):
        if opening:
            marks["setup_s"] = time.perf_counter() - t_start
        else:
            stop_trace()

    drv = driver.Driver(eng, make_request, mix.loop,
                        clients=int(mix.params.get("clients", 0)),
                        spans=True, on_window=on_window,
                        marks=[(max(0.0, seconds - TRACE_S), start_trace)]
                        if traced else [])
    say(f"set-up before warm traffic: {time.perf_counter() - t_start:.3f} s "
        f"(compile decode {compile_s['decode']:.3f} s, prefill "
        f"{compile_s['prefill']:.3f} s)")
    win = drv.run(warm, window, float(mix.params["warm_s"]), seconds, DRAIN_S)
    out = Served(drv.records, win, seconds, marks["setup_s"], calls.log,
                 progs, peak_bytes(devs), prof_dir)
    attempted = sum(1 for r in drv.records if r.in_window)
    lags = [r.added - r.due for r in drv.records if r.added]
    in_flight = sum(1 for r in drv.records if r.in_window and r.added
                    and not r.req.done)
    say(f"window {seconds} s: {attempted} requests due, "
        f"{len(driver.failed(drv.records, mix.loop))} failed, {in_flight} "
        f"in flight at the end; {len(win.steps)} steps; "
        f"generator lag p50 "
        f"{driver.percentile(lags, 50) if lags else 0.0:.6f} s, max "
        f"{max(lags, default=0.0):.6f} s")
    if win.load:
        slots, pages = np.asarray(win.load, float).T
        say(f"load in the window: slots holding a request mean "
            f"{slots.mean():.2f} (min {slots.min():.0f}, max {slots.max():.0f}) "
            f"of {len(eng.sched.slots)}; pages held mean "
            f"{100 * pages.mean():.1f}% (max {100 * pages.max():.1f}%) of "
            f"{eng.sched.kv.num_pages - 1}")
    del eng, drv
    gc.collect()
    return out


def run(argv=None, *, t_start: Optional[float] = None,
        paths: Paths = Paths(), require_tpu: bool = True,
        fault: Optional[Callable] = None) -> int:
    """One run; returns the exit code. ``fault(engine)`` (tests only)
    breaks the timed path after it is built."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    if not load_program():
        return 2
    bench = json.loads(paths.benchmark.read_text())
    cell = cell_entry(bench, args.workload)
    from bench import driver, model, peaks, trace, traffic

    spec = model.load_config(cell["config"], paths.configs)
    mix = traffic.load(cell["traffic"], paths.traffic)
    limits = load_limits(paths, cell["name"])
    wanted = metrics_of(bench, cell["name"], bool(args.trace))
    readers = {m["name"]: load_reader(paths.metrics / f"{m['name']}.py")
               for m in wanted}
    devs = find_chips(cell, require_tpu)
    if devs is None:
        return 1
    chip_peaks = peaks.peaks_for(devs[0].device_kind) if require_tpu else None
    say(f"compile cache: {enable_compile_cache(paths.cache)}")

    got = serve(spec, mix, args.seed, args.seconds, bool(args.trace), devs,
                t_start, paths, fault)
    ctx = Context(spec=spec, records=got.records, window=got.window,
                  window_s=got.window_s, setup_s=got.setup_s,
                  peaks=chip_peaks, calls=got.calls, programs=got.programs)
    device = device_info(devs, cell["chips"])
    device["memory_peak_bytes"] = got.memory_peak_bytes
    breakdown = None
    if args.trace:
        ctx.trace = trace.reduce(trace.find_xplane(str(got.prof_dir)))
        shutil.rmtree(got.prof_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = {
            "device_ops": device_ops(ctx.trace, got.programs),
            "idle_gaps": sorted(([k, v] for k, v in
                                 trace.idle_by_span(ctx.trace).items()),
                                key=lambda kv: -kv[1])[:10]}
    values = {}
    for m in wanted:
        v = readers[m["name"]](ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    t_ref = time.perf_counter()
    checks = check_outputs(got.records, spec, args.seed, limits)
    say(f"reference comparison took {time.perf_counter() - t_ref:.3f} s")
    failed = len(driver.failed(got.records, mix.loop))
    result = {"correct": verdict(checks, failed),
              "attempted": sum(1 for r in got.records if r.in_window),
              "failed": failed,
              "metrics": values, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


def device_ops(red, progs, n: int = 10) -> List[list]:
    """The ``n`` device operations with the most time in the traced
    window, named "<program> <kernel or place in the program>"."""
    tot: Dict[str, float] = {}
    for run in red.runs:
        for op in run.ops:
            key = f"{run.kind} {progs[run.kind].label(op)}"
            tot[key] = tot.get(key, 0.0) + op.dur
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
