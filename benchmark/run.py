"""Runs one cell once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips: it fails if JAX shows no TPU or
fewer chips than the cell asks for (no CPU fall-back), builds the system
under test, warms up every shape the cell's traffic uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as its last line.  It knows no
cell, configuration or metric by name: ``harness/loader.py`` finds their
files from ``BENCHMARK.json``.
"""

import time

T_START = time.perf_counter()        # process start, for setup_s

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# JAX's persistent compile cache: the program sets it as it is imported,
# where the environment says or else at the fixed path <checkout>/.jax_cache
# (singa_tpu/__init__.py, the one place); the benchmark compiles nothing
# before that import and sets no directory of its own

from benchmark.harness import loader, output  # noqa: E402
from benchmark.harness.output import say  # noqa: E402


def measure(cell, devices, seed, seconds, trace, t_start, **run_kw):
    """Session -> window -> check -> the result line.  The tests call this
    with devices of their own choosing."""
    from benchmark.harness import peaks as peaks_mod
    from benchmark.harness import stats
    from benchmark.harness import trace_reduce

    driver = loader.load_module("drivers", cell["traffic"]["kind"])
    session = driver.Session(cell, devices)
    run = session.run(seed, seconds, trace, **run_kw)
    setup_s = run["setup_end"] - t_start
    device = output.device_block(devices)     # the program's own peak
    if hasattr(session, "release"):
        session.release()

    t = time.perf_counter()
    numbers = session.check(seed, run)
    limits = cell["cell"]["limits"]
    correct = True
    for k, v in numbers.items():
        lim = limits[k]
        ok = lim is not None and v <= lim
        correct &= ok
        say(f"check: {k} = {v:.6g} (limit {lim}) "
            f"{'ok' if ok else 'NOT CORRECT'}")
    say(f"check took {time.perf_counter() - t:.1f} s (not in setup_s)")

    ctx = dict(run=run, cell=cell, device=device, trace=None)
    c, w = run["counters"], run["window_s"]
    # what an end-to-end metric IS lives here, where no later PR can
    # change it; which of them a cell reports is the manifest's to say
    e2e = {
        "train_tokens_per_s": lambda: c["tokens"] / w,
        "serve_tokens_per_s": lambda: c["processed_tokens"] / w,
        # over every request due in the window; one that failed or got no
        # answer counts as infinite
        "ttft_mean_ms": lambda: stats.mean(run["samples"]["ttft_ms"]),
        "token_gap_p95_ms": lambda: stats.percentile(
            run["samples"]["token_gap_ms"], 95),
        "setup_s": lambda: setup_s,
    }
    metrics, breakdown = {}, None
    if not trace:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = (e2e[m["name"]](), m["unit"])
    else:
        ctx["peaks"] = peaks_mod.peaks(device["kind"])
        tracer = run["tracer"]
        if tracer is not None and tracer.done:
            ctx["trace"] = tr = tracer.load()
            ctx["trace_window"] = t0, t1 = trace_reduce.window_of(tr)
            device["busy_s"] = trace_reduce.busy_seconds(tr, t0, t1)
            device["window_s"] = t1 - t0
            breakdown = trace_reduce.breakdown(tr, t0, t1)
            say(f"trace: {device['window_s']:.3f} s traced, device busy "
                f"{device['busy_s']:.3f} s; programs run: "
                f"{trace_reduce.module_counts(tr, t0, t1)}")
        for m in cell["per_layer"]:
            reader = loader.load_module("readers", m["file"]["reader"])
            v = reader.read(ctx, **m["file"]["params"])
            if v is not None:
                metrics[m["name"]] = (v, m["unit"])
    for k, (v, u) in metrics.items():
        say(f"metric: {k} = {v:.6g} {u}")
    return output.result_line(correct, run["attempted"], run["failed"],
                              metrics, device, breakdown)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = loader.load_cell(a.workload)
    devices = output.require_tpu(cell["chips"])
    say(f"cell {cell['name']}: {cell['why']}")
    say(f"device: {devices[0].device_kind} x {len(devices)}")
    print(measure(cell, devices, a.seed, a.seconds, bool(a.trace), T_START),
          flush=True)


if __name__ == "__main__":
    main()
