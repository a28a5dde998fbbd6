"""Drives a training cell: one ``Trainer``, stepped on the host clock.

Set-up builds the trainer over the bench's weights, and drives its first
``CHECK_STEPS`` steps through the same call and feed as the window (the
first compiles).  Their readings are taken there: each step's loss, the
first gradient per leaf as the optimizer got it (Adam's first moment after
one step over ``1 - b1``), the change of every leaf after the first step
and after the last, and Adam's two moments after the last.  The window
then steps the same trainer until ``--seconds`` have passed.  Once it has
closed and the trainer is freed, the reference runs
the same steps from the same weights, and the gaps between the two sides
are held against the cell's limits.
"""

from __future__ import annotations

import gc

import numpy as np

from bench.lib import arch, clock, profile, program, weights
from bench.lib.record import Record, Step
from bench.reference import checks

CHECK_STEPS = 3
# Leaves whose first reference gradient is below this share of the median
# leaf's move by weight decay and round-off alone; they sit out the change
# comparison.
FROZEN_SHARE = 1e-3
# The numbers ``compare`` gives that a run may hold against limits.
CHECKS = ("loss_gap", "grad_norm_gap", "grad_diff", "first_change_norm_gap",
          "change_norm_gap", "mu_norm_gap", "nu_norm_gap", "mu_median_gap", "nu_median_gap")


def relative_gaps(got: dict[str, float], want: dict[str, float],
                  keep=None) -> dict[str, float]:
    """Per leaf ``|got - want|`` over the larger of ``want`` and the median
    leaf's ``want``."""
    leaves = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in leaves]))
    return {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30) for k in leaves}


def canonical_arrays(model, tree) -> dict:
    """Host copies of a program tree's leaves, by their paths in the bench's
    weight layout (``model``: the architecture module)."""
    import jax

    return model.canonical({p: np.asarray(x) for p, x in
                            zip(checks.leaf_paths(tree), jax.tree.leaves(tree))})


def compare(prog: dict, ref: dict) -> dict[str, float]:
    """The numbers compared, each the worst leaf's, over the larger of the
    leaf's and the median leaf's reference norm: for the first gradient,
    the gap of norms and the norm of the difference (``ref["grad_diff_norms"]``,
    read against the other side's first moments; None where that reading
    was not taken); the gap of norms of the first step's change, of the
    change after the last check step, and of Adam's first and second
    moments after it; for the moments also the median leaf's gap
    (``*_median_gap``).  The loss gaps are read but not compared (PERF.md
    §2)."""
    med = float(np.median(list(ref["grad_norms"].values())))
    moving = {k for k, v in ref["grad_norms"].items() if v >= FROZEN_SHARE * med}
    grad = relative_gaps(prog["grad_norms"], ref["grad_norms"])
    gaps = {name: relative_gaps(prog[key], ref[key], keep=moving)
            for name, key in (("first_change_norm_gap", "first_change_norms"),
                              ("change_norm_gap", "change_norms"),
                              ("mu_norm_gap", "mu_norms"), ("nu_norm_gap", "nu_norms"))}
    out = {
        "grad_diff": None,
        "loss_gap": abs(prog["losses"][0] - ref["losses"][0]),
        "later_loss_gap": max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": max(grad.values()),
        "frozen_leaves": sorted(set(ref["grad_norms"]) - moving),
        "worst_grad_leaf": max(grad, key=grad.get),
    }
    for name, per_leaf in gaps.items():
        out[name] = max(per_leaf.values())
        out["worst_leaf." + name] = max(per_leaf, key=per_leaf.get)
    for moment in ("mu", "nu"):
        out[f"{moment}_median_gap"] = float(np.median(list(gaps[f"{moment}_norm_gap"].values())))
    if "grad_diff_norms" in ref:
        diff = {k: v / max(ref["grad_norms"][k], med, 1e-30)
                for k, v in ref["grad_diff_norms"].items()}
        out["grad_diff"] = max(diff.values())
        out["worst_leaf.grad_diff"] = max(diff, key=diff.get)
    return out


def run(ctx) -> Record:
    cell, seed = ctx.cell, ctx.seed
    c, t = cell.config, cell.traffic
    b1 = c["optimizer"]["b1"]
    model = arch.of(c, cell.root)
    canonical = model.canonical

    tr = program.trainer(model, c, weights.make(seed, c, model), total_steps=CHECK_STEPS)
    feed = ctx.traffic.stream(t, c, seed)
    steps: list[Step] = []

    def one_step() -> dict:
        s0 = clock.now()
        with profile.annotate("bench.step"):
            m = program.train_one_step(tr, feed)
        steps.append(Step(s0, clock.now(), int(t["seq_len"] * t["batch"]), float(m["loss"])))
        return m

    # Set-up: the first steps compile and give the program's readings.
    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        one_step()
        prog["losses"].append(steps[-1].loss)
        if i == 0:
            mu = checks.leaf_norms(tr.opt_state["mu"])
            prog["grad_norms"] = canonical({k: v / (1 - b1) for k, v in mu.items()})
            first_moments = canonical_arrays(model, tr.opt_state["mu"])
            init = model.to_program(weights.make(seed, c, model))
            prog["first_change_norms"] = canonical(checks.diff_norms(tr.params, init))
            del init
    init = model.to_program(weights.make(seed, c, model))
    prog["change_norms"] = canonical(checks.diff_norms(tr.params, init))
    del init
    prog["mu_norms"] = canonical(checks.leaf_norms(tr.opt_state["mu"]))
    prog["nu_norms"] = canonical(checks.leaf_norms(tr.opt_state["nu"]))
    gc.collect()
    steps.clear()

    # The window.
    seconds = min(ctx.seconds, t["trace_s"]) if ctx.trace else ctx.seconds
    lowered, setup_compile_s = ctx.clock.lowerings, ctx.clock.compile_s
    loads = []
    with ctx.capture(), profile.annotate(profile.WINDOW):
        w0 = clock.now()
        while clock.now() - w0 < seconds:
            m = one_step()
            if "expert_load" in m:
                loads.append(np.asarray(m["expert_load"], np.float64))
    w1 = steps[-1].end
    rec = Record(c, t, ctx.peaks, cell.chips, (w0, w1), ctx.age_at(w0))
    rec.steps = list(steps)
    rec.attempted = len(steps)
    rec.failed = sum(1 for s in steps if not np.isfinite(s.loss))
    rec.counts["setup_backend_compile_s"] = setup_compile_s
    rec.counts["compiles_in_window"] = ctx.clock.lowerings - lowered
    rec.counts["steps_in_window"] = len(steps)
    rec.counts["tokens_per_step"] = int(t["seq_len"] * t["batch"])
    if loads:
        per_layer = np.stack(loads).sum(0)  # [layers, E]
        rec.counts["expert_load_max_over_mean"] = [
            float(row.max() / max(row.mean(), 1e-30)) for row in per_layer
        ]
    rec.memory_peak_bytes = ctx.memory_peak()

    # Free the program's state, then run the reference in its place.
    del tr, feed
    gc.collect()
    ref = checks.train_readings(
        arch.reference(c, cell.root), weights.make(seed, c, model),
        ctx.traffic.stream(t, c, seed), model.reference_view(c), c["optimizer"],
        steps=CHECK_STEPS, against=first_moments,
    )
    del first_moments
    ref["change_norms"] = checks.diff_norms(ref.pop("params"), weights.make(seed, c, model))
    got = compare(prog, ref)
    rec.counts["program_losses"] = prog["losses"]
    rec.counts["reference_losses"] = ref["losses"]
    rec.counts["first_step_loss_gap"] = got["loss_gap"]
    rec.counts["later_loss_gap"] = got["later_loss_gap"]
    rec.counts["frozen_leaves"] = got["frozen_leaves"]
    for k, v in got.items():
        if k.startswith("worst_"):
            rec.counts[k] = v
    rec.checks = {k: got[k] for k in CHECKS}
    return rec
