"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and architecture module, its traffic mix, its
limits and its metrics are found by name through ``BENCHMARK.json``
(``bench/lib/spec.py``, ``bench/lib/arch.py``); nothing here names one.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiled window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared for ``correct``
beside its limit.  The same numbers are the last lines of standard error.
Counts that are not metrics go to earlier lines.  Off a TPU, or with fewer
chips than the cell needs, the run exits with code 3 and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import clock, device, profile, spec  # noqa: E402
from bench.lib.program import import_program  # noqa: E402

DRIVERS = {"train": "bench.lib.train", "serve": "bench.lib.serve"}
EXIT_NO_CHIP = 3


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, and the harness."""

    root: pathlib.Path
    cell: spec.Cell
    traffic: object  # the traffic mix's generator module
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    clock: clock.CompileClock
    started: float  # host clock when this script began
    age_at_start: float  # process age then
    trace_dir: str
    control: bool = False  # also read the float8 control (bench/controls.py)

    def age_at(self, t: float) -> float:
        """Seconds from process start to host-clock time ``t``."""
        return self.age_at_start + (t - self.started)

    def capture(self):
        return profile.capture(self.trace_dir) if self.trace else contextlib.nullcontext()

    def memory_peak(self) -> int | None:
        return device.memory_peak_bytes(self.devices)


def configure_jax(root: pathlib.Path) -> None:
    """The persistent compilation cache at a fixed path inside the checkout,
    holding every program, however quickly it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float, trace: bool,
             *, started: float, age_at_start: float, require_chip: bool = True,
             control: bool = False):
    """Run one cell; returns ``(result dict, record)``.  ``require_chip`` is
    for the harness's own tests, which drive a run on the CPU; ``control``
    for ``bench/controls.py``."""
    import importlib

    import jax

    cell = spec.load_cell(root, workload)
    if require_chip:
        devices = device.accelerator(cell.chips)
    else:
        devices = jax.devices()[: cell.chips]
    kind = devices[0].device_kind
    peaks = device.peaks(root, kind) if require_chip else {}
    gen = spec.traffic_module(cell)
    ctx = Context(
        root=root, cell=cell, traffic=gen, seed=seed, seconds=seconds, trace=trace,
        devices=devices, peaks=peaks, clock=clock.CompileClock(), started=started,
        age_at_start=age_at_start,
        trace_dir=str(root / ".bench_traces" / workload), control=control,
    )
    rec = importlib.import_module(DRIVERS[gen.DRIVER]).run(ctx)
    if trace:
        rec.trace = profile.Reduction(profile.load_events(ctx.trace_dir))

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.metric_reader(root, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {
        name: {"value": rec.checks.get(name), "limit": limit}
        for name, limit in cell.limits["checks"].items()
    }
    correct = rec.attempted > 0 and rec.failed == 0 and all(
        c["value"] is not None and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()
    )
    dev = dict(device.describe(devices), memory_peak_bytes=rec.memory_peak_bytes)
    result = {"correct": correct, "attempted": rec.attempted, "failed": rec.failed,
              "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = rec.trace.busy_s()
        dev["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    result["checks"] = checks
    return result, rec


def main(argv=None) -> int:
    started = time.perf_counter()
    age = clock.process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import_program(ROOT)
    configure_jax(ROOT)
    try:
        result, rec = run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), started=started, age_at_start=age)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for key, value in rec.counts.items():
        print(f"count {key}: {json.dumps(value)}")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
