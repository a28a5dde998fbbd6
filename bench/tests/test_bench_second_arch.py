"""A configuration of a second architecture joins the benchmark from new
files alone: its architecture module, its reference, its configuration and
limits, and entries appended to ``BENCHMARK.json``.  No file of the copied
benchmark changes, and its training cell runs ``correct`` on the CPU.  (Its
serving cell, run the same way, adds a few seconds to a test that is kept
under 20; it is left out.)

The architecture is a tiny dense decoder (``bench/tests/data/tiny_dense.py``,
``model_type`` ``"tiny_dense"``), which the program runs through its dense
path, with no experts.
"""

import hashlib
import json
import shutil

import pytest

from bench import run as bench_run
from bench.lib import arch, spec
from bench.lib.record import Record, Step, Tick
from bench.tests.roots import DATA, TINY_LIMITS, TRAIN, make_root

SEED = 2**31 + 91
SECONDS = 0.3
# New cell -> (its traffic mix, the tiny cell whose limits and metrics it takes).
CELLS = {"tiny-dense-train": ("tiny-lm", TRAIN)}


def digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def add_tiny_dense(root):
    """Adds the new architecture's files to the benchmark under ``root`` and
    appends its cells to ``BENCHMARK.json``; touches no file already there."""
    bench = root / "bench"
    copies = {
        bench / "models" / "tiny_dense.py": DATA / "tiny_dense.py",
        bench / "reference" / "tiny_dense_lm.py": DATA / "tiny_dense_lm.py",
        bench / "configs" / "tiny-dense.json": DATA / "tiny-dense.json",
    }
    limits = {bench / "limits" / f"{cell}.json": TINY_LIMITS[stand_in]
              for cell, (_, stand_in) in CELLS.items()}
    for dst in [*copies, *limits]:
        assert not dst.exists(), dst
    for dst, src in copies.items():
        shutil.copy(src, dst)
    for dst, lim in limits.items():
        dst.write_text(json.dumps(lim))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-dense", "source": "bench/tests/data/tiny-dense.json",
                            "file": "bench/configs/tiny-dense.json", "reduced": [],
                            "why": "tests"})
    for cell, (traffic, stand_in) in CELLS.items():
        spec["workloads"].append({"name": cell, "config": "tiny-dense", "traffic": traffic,
                                  "chips": 1, "why": "tests"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if stand_in in m.get("workloads", ()):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def appended_only(old, new):
    """``new`` is ``old`` with items appended to its lists, nothing else."""
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(appended_only(a, b) for a, b in zip(old, new)))
    if isinstance(old, dict):
        return (isinstance(new, dict) and new.keys() == old.keys()
                and all(appended_only(old[k], new[k]) for k in old))
    return old == new


def test_a_second_architecture_joins_from_new_files_alone(tmp_path):
    root = make_root(tmp_path)
    before = digests(root / "bench")
    spec_before = json.loads((root / "BENCHMARK.json").read_text())
    add_tiny_dense(root)
    assert appended_only(spec_before, json.loads((root / "BENCHMARK.json").read_text()))

    for cell in CELLS:
        result, rec = bench_run.run_cell(root, cell, SEED, SECONDS, False, started=0.0,
                                         age_at_start=0.0, require_chip=False)
        assert result["correct"], (cell, result["checks"])
        assert result["attempted"] > 0 and result["failed"] == 0
        assert "setup_s" in result["metrics"]
    after = digests(root / "bench")
    assert {k: after.get(k) for k in before} == before


def test_an_unknown_model_type_names_the_missing_file(tmp_path):
    root = make_root(tmp_path)
    with pytest.raises(FileNotFoundError, match=r"bench/models/no_such_arch\.py"):
        arch.of({"model_type": "no_such_arch"}, root)
    with pytest.raises(ValueError, match="not a benchmark name"):
        arch.of({"model_type": "../run"}, root)


def test_a_reader_whose_count_the_module_lacks_reads_nothing(tmp_path):
    # tiny_dense gives flops_per_token but no grouped-GEMM or serving count.
    root = make_root(tmp_path)
    add_tiny_dense(root)
    c = json.loads((root / "bench" / "configs" / "tiny-dense.json").read_text())
    rec = Record(c, {"seq_len": 32, "batch": 2}, {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                 1, (0.0, 1.0), 0.0)
    rec.steps = [Step(0.1, 0.2, 64, 1.0), Step(0.2, 0.3, 64, 1.0)]
    rec.ticks = [Tick(0, 0.1, 0.2, live=1, context_tokens=8)]
    rec.trace = object()  # never read: the missing count ends the reader first

    def read(metric):
        return spec.metric_reader(root, metric)(rec)

    assert read("mfu.train") == pytest.approx(
        100.0 * arch.of(c, root).flops_per_token(c, 32) * 640.0 / 1e12)
    assert read("mfu.serve") is None
    assert read("moe_gemm.roofline_share.train") is None
