"""Several windows in one process, over one set-up -- for the builder of
the benchmark, not for the driver: the rate sweep that finds a serve
cell's knee, the dozen seeds a limit is set from, and the control (the
reference one precision below the configuration's, which has to come out
as not correct).

    python3 benchmark/tools/many.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--rates 2,3,4] [--control int8,fp8] [--out file.jsonl]

Prints one JSON line per window: the end-to-end numbers, the numbers the
check compares, and with ``--control`` the same numbers from the control.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import loader, output, stats  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--control-only", action="store_true",
                    help="training cells: the control's numbers alone, "
                         "on one chip whatever the cell's chips")
    a = ap.parse_args(argv)
    cell = loader.load_cell(a.workload)
    driver = loader.load_module("drivers", cell["traffic"]["kind"])
    if a.control_only:
        output.require_tpu(1)
        for seed in (int(s) for s in a.seeds.split(",")):
            for precision in a.control.split(","):
                print(json.dumps({"seed": seed, "control": precision,
                                  **driver.control(cell, seed, precision)}),
                      flush=True)
        return
    devices = output.require_tpu(cell["chips"])
    session = driver.Session(cell, devices)
    # a training session's state is spent by its run: one for each seed
    per_seed = hasattr(session, "release")
    rates = [float(r) for r in a.rates.split(",") if r] or [None]
    lines = []
    for seed in (int(s) for s in a.seeds.split(",")):
        for rate in rates:
            t = time.perf_counter()
            if per_seed and session is None:
                session = driver.Session(cell, devices)
            run = session.run(
                seed, a.seconds, False,
                overrides={"rate_per_s": rate} if rate else None)
            c, w, sm = run["counters"], run["window_s"], run["samples"]
            row = {"seed": seed, "rate": rate, "window_s": w,
                   "attempted": run["attempted"], "failed": run["failed"],
                   "device": output.device_block(devices)}
            if "tokens" in c:
                row["train_tokens_per_s"] = c["tokens"] / w
            else:
                row["serve_tokens_per_s"] = c["processed_tokens"] / w
                row["generated_per_s"] = c["generated_tokens"] / w
                row["live_slots_mean"] = c["live_slots_mean"]
                row["blocks_used_peak"] = c["blocks_used_peak"]
                for k, q in (("ttft_ms", 50), ("ttft_ms", 90),
                             ("token_gap_ms", 50), ("token_gap_ms", 95),
                             ("engine_step_ms", 50),
                             ("queue_wait_ms", 50)):
                    if sm.get(k):
                        row[f"{k}_p{q}"] = stats.percentile(sm[k], q)
                row["ttft_ms_mean"] = stats.mean(sm["ttft_ms"])
            if per_seed:
                session.release()
            row["check"] = session.check(seed, run)
            for precision in filter(None, a.control.split(",")):
                row["control_" + precision] = session.check(
                    seed, run, precision)
            if per_seed:
                session = None
            row["took_s"] = time.perf_counter() - t
            lines.append(json.dumps(row))
            print(lines[-1], flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
