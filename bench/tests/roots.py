"""A throwaway benchmark root for the harness's tests: a copy of ``bench/``
with a ``BENCHMARK.json`` of CPU-sized cells from ``bench/tests/data``."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
TRAIN, SERVE = "tiny-train", "tiny-serve"
# The tiny cells' limits: above what the program reads at this size in
# bfloat16 (first-step loss 1e-4, first-gradient norms 1.4e-3 apart and
# 7e-3 by the norm of their difference, first change 4e-4, change after
# three steps 1.3e-3, first moments 1.4e-3 and second moments 4e-3 apart
# at the worst leaf, no served token below the reference's best), below
# what the float8
# control reads (first gradient 1.0e-2 to 1.8e-2 and 0.18 to 0.30; served
# tokens 2e-2 below the best) and the optimizer's faults (a learning rate
# 20% low: first change 0.18; b1 0.8: first moments 0.83; b2 0.99: second
# moments 0.79).
TINY_LIMITS = {
    TRAIN: {"checks": {"grad_norm_gap": 0.005, "grad_diff": 0.05, "first_change_norm_gap": 0.01,
                       "change_norm_gap": 0.05, "mu_median_gap": 0.01, "nu_median_gap": 0.03}},
    SERVE: {"checks": {"far_token_share": 0.01}, "far_gap": 0.01, "sample_tokens": 32,
            "pad_to": 64},
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """``tmp`` holding ``bench/`` and a ``BENCHMARK.json`` whose cells are
    ``tiny-train`` and ``tiny-serve`` on the tiny configuration."""
    root = pathlib.Path(tmp)
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny-moe.json", root / "bench" / "configs" / "tiny-moe.json")
    for name in ("tiny-lm", "tiny-closed-loop"):
        shutil.copy(DATA / f"{name}.json", root / "bench" / "traffic" / f"{name}.json")
    for cell, limits in TINY_LIMITS.items():
        (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(limits))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    # Each real cell's metrics go to the tiny cell of the same traffic kind.
    kinds = {"lm_tokens": TRAIN, "closed_loop": SERVE}
    stand_in = {
        w["name"]: kinds[json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())["kind"]]
        for w in bench["workloads"]
    }
    bench["configs"] = [{"name": "tiny-moe", "source": "bench/tests/data/tiny-moe.json",
                         "file": "bench/configs/tiny-moe.json", "reduced": [], "why": "tests"}]
    bench["workloads"] = [
        {"name": TRAIN, "config": "tiny-moe", "traffic": "tiny-lm", "chips": 1, "why": "tests"},
        {"name": SERVE, "config": "tiny-moe", "traffic": "tiny-closed-loop", "chips": 1, "why": "tests"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({stand_in[w] for w in m["workloads"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
