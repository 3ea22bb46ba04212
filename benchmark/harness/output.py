"""The device as JAX reports it, and the one line a run ends with."""

import json
import sys


def require_tpu(chips):
    """The cell's chips, or exit: no CPU fall-back, and no result line."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"benchmark: this cell needs {chips} TPU chip(s); JAX "
                 f"found {len(devs)} device(s) of platform "
                 f"{devs[0].platform!r} -- nothing was run")
    return devs[:chips]


def device_block(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def say(msg):
    print(msg, flush=True)


def result_line(correct, attempted, failed, metrics, device,
                breakdown=None):
    """``metrics``: {name: (value, unit)}; values as measured, unrounded."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": float(v), "unit": u}
                        for k, (v, u) in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
