"""Run one cell once: ``python -m chipbench.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.

One process, which owns the chip. It refuses to run — exit code 2,
nothing on stdout — unless JAX reports the accelerator and as many chips
as the cell asks for. The last line of stdout is the result, one JSON
object; everything else a run has to say goes on earlier lines and into
``chipbench_out/<workload>/``.
"""

from __future__ import annotations

import time

T_PROCESS0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from chipbench import spec, stats  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REQUIRED_PLATFORM = "tpu"
OUT_DIR = "chipbench_out"
LATE_WARN_MS = 2.0


def _devices(chips: int):
    """The chips this cell needs, or exit 2 with nothing on stdout."""
    import jax

    devices = jax.devices()
    if devices[0].platform != REQUIRED_PLATFORM:
        print(
            f"chipbench: needs a {REQUIRED_PLATFORM}, jax reports "
            f"{devices[0].platform!r}; a number from another platform is "
            "not a measurement",
            file=sys.stderr,
        )
        raise SystemExit(2)
    if len(devices) < chips:
        print(
            f"chipbench: the cell asks for {chips} chips, jax reports "
            f"{len(devices)}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return devices


def _read_metrics(cell: spec.Cell, group: str, record) -> dict:
    out = {}
    for metric in cell.metrics(group):
        name = metric["name"]
        try:
            value = spec.load_reader(cell.root, group, name)(record)
        except stats.TooFewSamples as exc:
            record.problems.append(f"{name}: {exc}")
            continue
        if value is not None:
            out[name] = {"value": float(value), "unit": metric["unit"]}
    return out


def _program_spans(record):
    """The program's spans as intervals on the profiler trace's clock."""
    dev = record.device
    wall0 = record.steady_wall_s
    for s in record.spans:
        if s.dur_ms > 0:
            start = dev.steady[0] + (s.ts_ms / 1e3 - wall0) * 1e9
            yield (s.name, start, start + s.dur_ms * 1e6)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool) -> dict:
    cell = spec.load_cell(root, workload)
    devices = _devices(int(cell.workload["chips"]))
    out_dir = os.path.join(root, OUT_DIR, workload)
    if trace:
        shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)

    driver = spec.load_driver(root, cell.config["served_path"])(
        out_dir, cell.config, cell.mix, seed, seconds, trace, T_PROCESS0
    )
    try:
        driver.set_up()
        record = driver.measure()
    finally:
        driver.close()
    record.device_kind = devices[0].device_kind

    group = "per_layer" if trace else "end_to_end"
    metrics = _read_metrics(cell, group, record)
    late = sorted(record.lateness_ms)
    late_p95 = late[int(0.95 * (len(late) - 1))] if late else 0.0
    if late_p95 > LATE_WARN_MS:
        print("chipbench: the generator ran late: p95 of sent - due is "
              f"{late_p95:.3f} ms")
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": record.memory_peak_bytes,
    }
    result = {
        "correct": not record.problems,
        "attempted": record.attempted,
        "failed": record.failed,
        "metrics": metrics,
        "device": device,
    }
    if record.device is not None:
        device["busy_s"] = record.device.busy_s()
        device["window_s"] = record.device.window_s
        result["breakdown"] = {
            "device_ops": record.device.top_ops(),
            "idle_gaps": record.device.idle_gaps(_program_spans(record)),
        }
    detail = {
        "workload": workload,
        "seed": seed,
        "seconds": seconds,
        "trace": int(trace),
        "samples": len(record.samples_ms),
        "problems": record.problems,
        "setup": record.setup,
        "shapes": record.shapes,
        "counters": {
            k: v for k, v in sorted(record.counters.items())
            if v and k.split(".")[0] in
            ("chipbench", "decision", "ops", "fib", "kvstore", "telemetry")
            and not k.endswith((".avg", ".max", ".min", ".p50", ".p95",
                                ".p99", ".sum"))
        },
    }
    print("detail: " + json.dumps(detail, sort_keys=True))
    with open(os.path.join(out_dir, "last_run.json"), "w",
              encoding="utf-8") as f:
        json.dump({"detail": detail, "result": result,
                   "samples_ms": record.samples_ms,
                   "lateness_ms": record.lateness_ms}, f, indent=1)
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    result = run_cell(
        ROOT, args.workload, args.seed, args.seconds, bool(args.trace)
    )
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
