"""What the metric readers in bench/metrics/ compute. A reader file is
named for its quantity and the end-to-end metric it moves
(``decode_step_ms.itl`` moves ``itl_p95_ms``); a cell that reports
another end-to-end metric gets its own file for the same quantity,
calling the same function here."""
from __future__ import annotations

from typing import List, Tuple

from bench import trace, work


def runs(ctx, kind: str) -> List[Tuple[object, object]]:
    """(device run, host work) pairs of the traced window's ``kind``
    calls ("decode" or "prefill"), in order; [] when there is no trace
    or the trace and the host's log disagree on the number of calls."""
    if ctx.trace is None:
        return []
    got = [r for r in ctx.trace.runs if r.kind == kind]
    log = ctx.calls.get(kind, [])
    if not got or len(got) != len(log):
        return []
    return list(zip(got, log))


def share(useful_s: float, spent_s: float):
    """A share of a peak in percent; None where no time was spent."""
    return 100.0 * useful_s / spent_s if spent_s > 0 else None


def host_ms(ctx):
    """Host time per engine step in which the device had nothing to
    run: each ``bench.step`` span of the traced window less the
    device's busy time inside it, averaged over the steps, in ms."""
    if ctx.trace is None:
        return None
    steps = [e for e in ctx.trace.host if e.name == "bench.step"]
    if not steps:
        return None
    idle = [e.dur - trace.busy_within(ctx.trace, e.start, e.end)
            for e in steps]
    return 1e3 * sum(idle) / len(idle)


def program_ms(ctx, kind: str):
    """Device time per run of the ``kind`` program, in ms."""
    pairs = runs(ctx, kind)
    if not pairs:
        return None
    return 1e3 * sum(r.module.dur for r, _ in pairs) / len(pairs)


def mfu(ctx, kind: str):
    """Useful model operations of the ``kind`` runs (bench/work.py)
    over their device time times the chip's bf16 peak, in %."""
    pairs = runs(ctx, kind)
    if not pairs or ctx.peaks is None:
        return None
    if kind == "decode":
        flops = sum(work.decode_flops(ctx.spec, lens) for _, lens in pairs)
    else:
        flops = sum(work.prefill_flops(ctx.spec, s, n) for _, (s, n) in pairs)
    spent = sum(r.module.dur for r, _ in pairs)
    return share(flops / ctx.peaks["flops_bf16"], spent)


def idle_share(ctx):
    """Share of the traced window in which no operation ran on the
    device, in %."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def flash_decode_roofline(ctx):
    """The decode-attention kernel's share of its roofline: the least
    time the chip needs for the active slots' K/V at their real lengths
    (and their queries), over the kernel's device time in the decode
    runs, in %. None where the decode program holds no such kernel."""
    pairs = runs(ctx, "decode")
    prog = ctx.programs.get("decode")
    if not pairs or prog is None or ctx.peaks is None:
        return None
    is_kernel = prog.is_kernel("flash_decode")
    least = spent = 0.0
    for run, lens in pairs:
        t = trace.kernel_seconds(run, is_kernel)
        if t is None:
            return None
        least += work.roofline_seconds(
            *work.flash_decode_work(ctx.spec, lens), ctx.peaks)
        spent += t
    return share(least, spent)
