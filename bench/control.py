#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--out gaps.jsonl]

For each seed, one process runs the cell as `bench/run.py` does (set-up and
a window of ``--seconds``), then reads, over the same sample of served
requests, the gap of every served token (the program's reading) and of the
token an fp8 reference puts first at the same position (the control's).
It prints, per seed, both readings under the statistics the configuration
can name, and the verdict that `bench/run.py`'s own comparison gives each:
the program's has to come out correct, the control's not. ``--out`` keeps
every gap. The lower reading of the limit is the largest program reading
over the seeds, the upper the smallest control reading. The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

STATISTICS = ("max", "p90", "p50", "mean", "over0.3", "over0.5", "over0.7")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--out", help="a file to keep every gap in (JSON lines)")
    args = ap.parse_args()
    from harness import check, spec
    cell = spec.find_cell(spec.load_spec(), args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ref = spec.reference_module(cell)
    conf, max_seq = cell.config, cell.traffic["engine"]["max_seq"]
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.time()
        rn, params, _ = run.serve(cell, seed, args.seconds, False, spec.ROOT,
                                  devices, t_start=t0)
        sample = check.pick(rn.window.requests, conf["check"]["sample_requests"],
                            seed)
        t1 = time.time()
        program, control = check.readings(ref, params, conf, max_seq, sample)
        row = {"seed": seed, "tokens": int(program.size),
               "requests": len(sample), "check_s": time.time() - t1,
               "seconds": time.time() - t0}
        for who, g in (("program", program), ("control", control)):
            row[who] = {s: check.summary(g, s) for s in STATISTICS}
            row[who]["correct"] = check.verdict(g, conf["check"], 0)[0]
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps({"seed": seed, "program": program.tolist(),
                                    "control": control.tolist()}) + "\n")
        del params, rn
    stat = conf["check"]["gap_statistic"]
    print(json.dumps({"statistic": stat,
                      "lower": max(r["program"][stat] for r in rows),
                      "upper": min(r["control"][stat] for r in rows),
                      "seeds": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
