"""Closed loop of one client: the cell's instance solved back to back.

Set-up makes the configuration's instance on the device through the
family that its ``family`` names (``bench/families/<family>.py``, whose
docstring says what the run's seed does to it), sharded over the chips
under the ``1d`` layout, opens a
``repro.api.Session`` with the configuration's options, and runs one whole
solve: ``IPIOptions`` are static, so the tolerance and the iteration caps
are compiled in and only a whole solve warms every program.  The window
then calls ``Session.solve`` on the same instance until ``--seconds`` have
passed (no solve starts after that); each solve ends when its result is on
the host.

A traced run traces one more warm solve instead.  It records the family's
kernel facts, the least bytes and operations of one call of each kernel on
one chip's rows (a shard's rows gather from the whole exchanged vector)
and, on a mesh, of one all-gather of the values.  On one chip it then
times the family's kernels alone on the cell's own table, each under a
module name of its own.
"""

from __future__ import annotations

import time

import numpy as np

from bench import families, reference
from bench import trace as tr
from bench.harness import TRACE_DIR, Outcome, peak_bytes


def _options(cfg: dict) -> dict:
    """The Session options: the configuration's method, precision,
    tolerance and layout, with its ``options`` (extra Session options)
    over them."""
    return {"-method": cfg["method"], "-dtype": cfg["dtype"],
            "-atol": cfg["atol"], "-layout": cfg["layout"],
            "-verbose": False, **cfg.get("options", {})}


def run(ctx) -> Outcome:
    import jax

    from repro.api import Session

    cfg = ctx.config
    if "family" not in cfg:
        raise ValueError(f"configuration {cfg.get('name')!r} names no "
                         f"family (bench/families/<family>.py)")
    family = families.load(cfg["family"])
    n = cfg["n"]
    clock0 = ctx.clock.read()
    sess = Session(_options(cfg))
    mesh, _ = sess.placement()
    t0 = time.monotonic()
    with ctx.span("build"):
        table = family.table(cfg, ctx.seed, mesh)
        jax.block_until_ready(table)
    build_s = time.monotonic() - t0
    ctx.log("build", family=family.name, n=n, gamma=cfg["gamma"],
            build_s=build_s, table_bytes=sum(a.nbytes for a in table),
            peak_bytes=peak_bytes(ctx.devices))
    mdp = family.mdp(cfg, table)
    with ctx.span("warmup"):
        t0 = time.monotonic()
        warm = sess.solve(mdp)
    ctx.log("warmup", solve_s=time.monotonic() - t0,
            outer=warm.outer_iterations, inner=warm.inner_iterations,
            residual=warm.residual, converged=warm.converged,
            peak_bytes=peak_bytes(ctx.devices))
    setup_compile = ctx.clock.delta(ctx.clock.read(), clock0)
    facts = {"setup_compile_s": setup_compile["seconds"],
             "build_s": build_s, "n_devices": len(ctx.devices)}
    kernels = None
    if ctx.trace:
        from bench import counts

        chips = 1 if mesh is None else mesh.size
        facts["peak"] = counts.peaks(ctx.devices[0].device_kind)
        facts["kernels"] = family.kernel_facts(cfg, table, n // chips, n)
        if mesh is None:
            kernels = family.kernel_calls(ctx, cfg, table, warm.v,
                                          warm.policy)
        else:
            facts["exchange"] = {"bytes": counts.allgather_bytes(n, chips)}
        if kernels is not None:
            for name, facts_k in facts["kernels"].items():
                facts_k.update(module="bench_" + name,
                               calls=families.KERNEL_REPEATS)
    setup_s = time.monotonic() - ctx.t_start
    ctx.log("setup", setup_s=setup_s, compile_s=setup_compile["seconds"],
            programs=setup_compile["programs"],
            cache_hits=setup_compile["cache_hits"])

    answers, ends = [], []
    before = ctx.clock.read()
    t_win = time.monotonic()
    if ctx.trace:
        with tr.capture(TRACE_DIR):
            with ctx.span("solve"):
                answers.append(sess.solve(mdp))
            ends.append(time.monotonic())
            if kernels is not None:
                kernels()
    else:
        while time.monotonic() - t_win < ctx.seconds:
            with ctx.span("solve"):
                answers.append(sess.solve(mdp))
            ends.append(time.monotonic())
            r = answers[-1]
            ctx.log("solve", i=len(answers), wall_s=ends[-1] - (
                ends[-2] if len(ends) > 1 else t_win),
                outer=r.outer_iterations, inner=r.inner_iterations,
                residual=r.residual, converged=r.converged)
    in_window = ctx.clock.delta(ctx.clock.read(), before)
    peak = peak_bytes(ctx.devices)
    ctx.log("window", solves=len(answers), programs=in_window["programs"],
            memory_peak_bytes=peak)
    sess.close()
    facts["solves"] = [(r.outer_iterations, r.inner_iterations)
                       for r in answers]

    out = Outcome(
        e2e={"setup_s": setup_s, "solve_s": (ends[-1] - t_win) / len(ends),
             "peak_hbm_bytes": peak},
        facts=facts, checks={}, attempted=len(answers),
        failed=sum(not r.converged for r in answers),
        memory_peak_bytes=peak)
    if ctx.trace:
        trace = tr.load(tr.xplane_path(TRACE_DIR))
        s = tr.span(trace, "bench.solve")
        busy = tr.busy_ns(trace, s.start, s.end)
        facts.update(trace=trace, window="solve", window_ns=(s.start, s.end))
        out.busy_s = float(np.mean(list(busy.values()))) / 1e9 if busy \
            else 0.0
        out.window_s = s.dur / 1e9
        out.breakdown = tr.breakdown(trace, s.start, s.end)
    out.checks = check_answers(ctx, family, table, answers)
    return out


def check_answers(ctx, family, table, answers) -> dict:
    """Every distinct answer of the window against the reference; the
    worst reading of each number beside its limit."""
    cfg = ctx.config
    worst = {"residual": 0.0, "greedy_gap": 0.0}
    seen = set()
    t0 = time.monotonic()
    blocks = None
    for r in answers:
        key = (np.asarray(r.v).tobytes(), np.asarray(r.policy).tobytes())
        if key in seen:
            continue
        seen.add(key)
        if blocks is None:
            blocks = list(family.host_blocks(cfg, table))
        got = reference.evaluate(blocks, cfg["gamma"], r.v, r.policy,
                                 cfg["n"])
        for name, value in got.items():
            worst[name] = max(worst[name], value)
    ctx.log("reference", distinct_answers=len(seen),
            seconds=time.monotonic() - t0, **worst)
    return {name: (value, cfg["limits"][name])
            for name, value in worst.items()}
