"""Readings that set a cell's limits, on the chip at the cell's own size.

    python bench/controls.py --workload <cell> --seeds 11,12,13 [--seconds 45]
        [--program 0|1] [--faults fp8,half_batch,lr_low,b1_low,b2_high]

For each seed, in one process, prints one JSON line of readings:

* training cells — with ``--program 1`` a run of the cell with a window of
  ``--seconds`` and the numbers it compared; then for each of ``--faults``
  the reference put in the program's place, in float8 (the control), with
  half of each batch left out of the loss, or with a fault planted in its
  optimizer (``checks.OPTIMIZER_FAULTS``), compared with the float32
  reference by the cell's own numbers (``bench/lib/train.py`` ``compare``);
* serving cells — a run of the cell with a window of ``--seconds`` (long
  enough to finish the mix's longest requests), reading on the same sample
  the program's logit gaps and the control's (the gap of the token the
  float8 reference puts first at each served position), summarised as the
  widest gap and the shares of tokens over a few gap sizes.

The benchmark's own runs never run this; it is how the limits in
``bench/limits/<cell>.json`` were read (PERF.md).  Off a TPU it exits with
code 3.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402
from bench.lib import arch, clock, device, program, spec, train, weights  # noqa: E402
from bench.reference import checks  # noqa: E402


def train_controls(cell, seed: int, faults: list[str]) -> dict:
    c, t = cell.config, cell.traffic
    gen = spec.traffic_module(cell)
    model, ref = arch.of(c, cell.root), arch.reference(c, cell.root)
    view = model.reference_view(c)
    b1 = c["optimizer"]["b1"]

    def readings(precision, fault=None, against=None):
        r = checks.train_readings(ref, weights.make(seed, c, model), gen.stream(t, c, seed),
                                  view, c["optimizer"], steps=train.CHECK_STEPS,
                                  precision=precision, fault=fault, against=against,
                                  keep_first_moments=against is None)
        r["change_norms"] = checks.diff_norms(r.pop("params"),
                                              weights.make(seed, c, model))
        return r

    def in_place_of_program(r):
        # The first gradient as the optimizer got it, read back from the
        # first moment with the configuration's b1, as the program's is.
        moments = r.pop("first_moments")
        r["grad_norms"] = {k: float(np.linalg.norm(m.astype(np.float32))) / (1 - b1)
                           for k, m in moments.items()}
        return moments

    out, base = {}, None
    for label in faults:
        if label in checks.OPTIMIZER_FAULTS:
            side = readings("f32", label)
            in_place_of_program(side)
            base = base or readings("f32")
            base.pop("first_moments", None)
        else:
            side = readings(*(("fp8",) if label == "fp8" else ("f32", label)))
            against = in_place_of_program(side)
            here = readings("f32", against=against)
        got = train.compare(side, base if label in checks.OPTIMIZER_FAULTS else here)
        out[label] = {k: got[k] for k in ("later_loss_gap",) + train.CHECKS}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--program", type=int, choices=(0, 1), default=0)
    ap.add_argument("--faults", default="fp8,half_batch")
    args = ap.parse_args()
    program.import_program(ROOT)
    bench_run.configure_jax(ROOT)
    cell = spec.load_cell(ROOT, args.workload)
    try:
        device.accelerator(cell.chips)
    except device.NoAccelerator as e:
        print(f"controls: {e}", file=sys.stderr)
        return bench_run.EXIT_NO_CHIP
    kind = spec.traffic_module(cell).DRIVER
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if kind == "train":
            out = {}
            if args.program:
                result, rec = bench_run.run_cell(
                    ROOT, args.workload, seed, args.seconds, False, started=t0,
                    age_at_start=clock.process_age_s())
                out["program"] = dict(rec.checks)
                out["program"].update((k, v) for k, v in rec.counts.items()
                                      if k.startswith("worst"))
                out["program"].update(correct=result["correct"],
                                      losses=rec.counts.get("program_losses"),
                                      ref_losses=rec.counts.get("reference_losses"))
                del result, rec
            out.update(train_controls(cell, seed, [f for f in args.faults.split(",") if f]))
        else:
            result, rec = bench_run.run_cell(
                ROOT, args.workload, seed, args.seconds, False, started=t0,
                age_at_start=clock.process_age_s(), control=True)
            out = {"program": rec.counts.get("program"), "fp8": rec.counts.get("control_fp8"),
                   "correct": result["correct"]}
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
