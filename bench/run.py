#!/usr/bin/env python3
"""Runs one benchmark cell and prints its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of ``BENCHMARK.json``: it refuses to run without the
chips the cell asks for, makes seeded weights on the device, builds the
engine the launcher builds, fills the caches and warms every program the
window runs (set-up), drives the engine for ``--seconds`` (the window), then
checks what was served against the configuration's plain reference. With
``--trace 0`` the metrics are the cell's end-to-end ones; with ``--trace 1``
the first seconds of the window are profiled and the per-layer metrics are
reported. Diagnostics go to standard error; the numbers compared for
``correct`` are its last lines.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TRACE_SECONDS = 6.0          # the profiled part of a --trace 1 window


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: Path | None = None, require_chip: bool = True,
         alter_engine=None) -> int:
    """Exit code 0 with a result line, or non-zero with none. ``root`` is the
    checkout holding BENCHMARK.json; ``require_chip=False`` and
    ``alter_engine`` (a function given the built engine) exist for the
    harness's own tests."""
    args = parse(argv)
    from harness import spec
    root = root or spec.ROOT
    try:
        cell = spec.find_cell(spec.load_spec(root), args.workload, root)
    except (spec.SpecError, KeyError) as e:
        say(f"bench: {e}")
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    say(f"bench: devices {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform})")
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        say(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
            f"found {len(devices)} {devices[0].platform} device(s)")
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = measure(cell, args, root, devices, alter_engine)
    except Exception as e:
        say(f"bench: run failed: {type(e).__name__}: {e}")
        raise
    print(json.dumps(result), flush=True)
    return 0


def serve(cell, seed: int, seconds: float, traced: bool, root, devices,
          alter_engine=None, t_start: float = T_START):
    """Set-up and window of one run: returns its `Run` record, the weights,
    and the profiler's directory (``traced``). The engine is released before
    this returns, so what follows runs on a device holding only the weights."""
    from harness import driver, generator, program, roofline, weights
    from harness.record import Run

    program.import_program(root)
    from repro.launch.compile_cache import enable_compile_cache
    say(f"bench: compile cache {enable_compile_cache()}")
    conf, mix = cell.config, cell.traffic
    eng = mix["engine"]
    cfg = program.model_config(conf)
    params = weights.make_params(program.param_shapes(cfg), seed)
    engine = program.build_engine(cfg, params, eng, conf["serving"])
    if alter_engine is not None:
        alter_engine(engine)
    items = generator.generate(mix, seed, conf["vocab_size"])
    ticklog = driver.TickLog(engine)
    live = driver.setup_closed(engine, items, mix)
    counter, gclock = driver.CompileCounter(), driver.GcClock()
    tracer = logdir = None
    if traced:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = driver.Tracer(logdir, min(TRACE_SECONDS, seconds))
    counter.arm()
    gclock.arm()
    before = program.counters(engine)
    win = driver.window_closed(engine, live, seconds, tracer)
    after = program.counters(engine)
    compiles = counter.disarm()
    pauses = gclock.disarm()
    say(f"bench: garbage collections inside the window: {len(pauses)}, "
        f"{sum(p for _, p in pauses):.6f} s in all, longest "
        f"{max((p for _, p in pauses), default=0.0):.6f} s")
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:cell.chips])
    mem = devices[0].memory_stats() or {}
    say(f"bench: peak_bytes_in_use {peak_bytes} (device 0 bytes_limit "
        f"{mem.get('bytes_limit')})")
    kind = devices[0].device_kind
    run = Run(cell=cell, seconds=seconds, t_start=t_start, window=win,
              ticks=ticklog.ticks, before=before, after=after,
              sizes=roofline.Sizes.of(conf, eng["max_seq"]),
              peak=roofline.peak_for(kind) if devices[0].platform == "tpu"
              else roofline.Peak(1.0, 1.0),
              compiles=compiles, peak_bytes=int(peak_bytes))
    ticklog.engine = ticklog.fn = None
    del engine, ticklog, live
    gc.collect()
    return run, params, logdir


def measure(cell, args, root, devices, alter_engine) -> dict:
    from harness import check, trace
    from harness.spec import metric_reader, reference_module

    conf, eng = cell.config, cell.traffic["engine"]
    run, params, logdir = serve(cell, args.seed, args.seconds, bool(args.trace),
                                root, devices, alter_engine)
    win = run.window
    names = trace.load_names()
    if args.trace:
        from harness.driver import Tracer
        paths = sorted(Path(logdir).rglob("*.xplane.pb"))
        form = trace.events_from_xplane(str(paths[-1]), names, Tracer.NAME)
        shutil.rmtree(logdir, ignore_errors=True)
        run.trace = trace.reduce(form, names)
        say(f"bench: trace: device busy {run.trace.busy_s:.6f} s of "
            f"{run.trace.window_s:.6f} s; kernel calls {run.trace.kernel_calls} "
            f"for {len(run.traced_ticks())} traced ticks x "
            f"{run.sizes.layers} layers")
    say(f"bench: window {run.window_s:.6f} s, {len(run.window_ticks())} ticks, "
        f"{run.tokens()} tokens, set-up {win.t0 - T_START:.3f} s")
    say(f"bench: compilations inside the window: {run.compiles}")
    ts = [t for t, _ in run.ticks if win.t0 <= t <= win.t1]
    if len(ts) > 1:
        steps = [b - a for a, b in zip(ts, ts[1:])]
        i = max(range(len(steps)), key=steps.__getitem__)
        say(f"bench: tick intervals: median {sorted(steps)[len(steps) // 2]:.6f} s, "
            f"longest {steps[i]:.6f} s at {ts[i] - win.t0:.3f} s into the window")

    metrics = {}
    entries = cell.per_layer if args.trace else cell.end_to_end
    for m in entries:
        value = metric_reader(cell, m, "layer" if args.trace else "e2e")(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for note in _bounds(run):
        say(note)

    attempted = len(win.requests)
    failed = sum(1 for r in win.requests if r.stop_reason in check.BAD_STOPS)
    sample = check.pick(win.requests, conf["check"]["sample_requests"], args.seed)
    t_check = time.time()
    gaps = check.served_gaps(reference_module(cell), params, conf,
                             eng["max_seq"], sample)
    correct, checked = check.verdict(gaps, conf["check"], failed)
    say(f"bench: check over {len(sample)} requests, {gaps.size} served tokens, "
        f"{time.time() - t_check:.3f} s; gaps: " + ", ".join(
            f"{s} {check.summary(gaps, s):.6f}"
            for s in ("max", "p90", "p50", "mean", "over0.3", "over0.5", "over0.7")))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind, "count": len(devices),
                         "memory_peak_bytes": run.peak_bytes}}
    if run.trace is not None:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["checked"] = checked
    for name, c in checked.items():
        say(f"checked: {name} {c['value']!r} (limit {c['limit']!r})")
    return result


def _bounds(run) -> list[str]:
    """Which bound sets each roofline share."""
    from harness import roofline
    out = []
    step = run.least_step_time()
    if step is not None:
        out.append(f"bench: decode step least time {step[0]:.9f} s per tick, "
                   f"{step[1]}-bound")
    for kernel, need in (("score_est", roofline.score_est_need),
                         ("flash_decode", roofline.flash_decode_need)):
        share = run.kernel_share(kernel, need)
        if share is not None:
            out.append(f"bench: {kernel} roofline share {share[0]!r}, "
                       f"{share[1]}-bound")
    return out


if __name__ == "__main__":
    sys.exit(main())
