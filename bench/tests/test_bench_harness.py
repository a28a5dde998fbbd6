"""The harness end to end on the CPU, at a tiny width: it finds cells,
configurations and metrics by name alone, refuses to run off a TPU, and
``correct`` comes out false when the timed path is broken underneath.

These runs skip only the look for a chip (``require_chip=False``); the rest
of a run is the one the chip runs.  Limits here are the tiny cells' own
(``bench/tests/roots.py``), not the benchmark's.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench.tests.roots import SERVE, TRAIN, make_root

REPO = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 77  # seeds may exceed 32 bits
SECONDS = 0.3


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """One persistent compilation cache for this module's runs, so repeated
    programs compile once; the process's settings are restored after."""
    import jax

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def run(root, cell, trace=False):
    result, _ = bench_run.run_cell(root, cell, SEED, SECONDS, trace, started=0.0,
                                   age_at_start=0.0, require_chip=False)
    return result


def test_new_config_cell_and_metric_are_found_by_name(tmp_path, compile_cache):
    root = make_root(tmp_path)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "tiny-moe.json").read_text())
    cfg["name"] = "tiny-moe-wide-vocab"
    cfg["vocab_size"] = 2 * cfg["vocab_size"]
    (bench / "configs" / "tiny-moe-wide-vocab.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "tiny-lm.json").read_text())
    traffic["seq_len"] = 16
    (bench / "traffic" / "tiny-lm-short.json").write_text(json.dumps(traffic))
    shutil.copy(bench / "limits" / f"{TRAIN}.json", bench / "limits" / "tiny-new.json")
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(rec):\n    return len(rec.steps_in_window()) or None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-moe-wide-vocab", "source": "tests",
                            "file": "bench/configs/tiny-moe-wide-vocab.json", "reduced": [],
                            "why": "tests"})
    spec["workloads"].append({"name": "tiny-new", "config": "tiny-moe-wide-vocab",
                              "traffic": "tiny-lm-short", "chips": 1, "why": "tests"})
    spec["end_to_end"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                               "bound": 0.05, "source": "host_clock", "workloads": ["tiny-new"]})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-new")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    result = run(root, "tiny-new")
    assert result["correct"], result["checks"]
    assert result["metrics"]["steps_seen"]["value"] > 0
    assert set(result["metrics"]) == {"steps_seen", "train_tokens_per_s", "setup_s"}
    assert list(result)[-1] == "checks"
    # The harness itself names no cell, configuration or metric.
    source = (REPO / "bench" / "run.py").read_text()
    for name in [w["name"] for w in spec["workloads"]] + [c["name"] for c in spec["configs"]] \
            + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]:
        assert name not in source


def test_serving_cell_is_correct_and_reports_its_metrics(tmp_path, compile_cache):
    result = run(make_root(tmp_path), SERVE, trace=True)
    assert result["correct"], result["checks"]
    assert {"serve.decode_tick_ms", "serve.prefill_ms_per_ktok"} <= set(result["metrics"])
    assert result["attempted"] > 0 and result["failed"] == 0


def _state_unchanged(monkeypatch):
    import repro.train.train_step as ts

    monkeypatch.setattr(ts, "adamw_update", lambda p, g, s, cfg: (p, s, {"grad_norm": 0.0}))


def _half_batch(monkeypatch):
    import repro.train.train_step as ts

    real = ts.loss_fn

    def half(params, batch, *args):
        b = batch["tokens"].shape[0] // 2
        return real(params, {k: v[:b] for k, v in batch.items()}, *args)

    monkeypatch.setattr(ts, "loss_fn", half)


def _optimizer_off(monkeypatch, **changed):
    import dataclasses

    import repro.train.train_step as ts

    real = ts.adamw_update
    monkeypatch.setattr(ts, "adamw_update", lambda p, g, s, cfg: real(
        p, g, s, dataclasses.replace(cfg, **{k: f(cfg) for k, f in changed.items()})))


def _lr_low(monkeypatch):
    _optimizer_off(monkeypatch, lr=lambda cfg: 0.8 * cfg.lr)


def _b1_low(monkeypatch):
    _optimizer_off(monkeypatch, b1=lambda cfg: 0.8)


def _b2_high(monkeypatch):
    _optimizer_off(monkeypatch, b2=lambda cfg: 0.99)


def _token_altered(monkeypatch):
    import repro.serve.batching as batching

    real = batching.make_serve_step

    def altered(cfg, *args, **kwargs):
        step = real(cfg, *args, **kwargs)

        def next_token_off_by_one(*a, **k):
            out = step(*a, **k)
            return ((out[0] + 1) % cfg.vocab_size, *out[1:])

        return next_token_off_by_one

    monkeypatch.setattr(batching, "make_serve_step", altered)


@pytest.mark.parametrize("cell,fault", [
    (TRAIN, _state_unchanged),
    (TRAIN, _half_batch),
    (TRAIN, _lr_low),
    (TRAIN, _b1_low),
    (TRAIN, _b2_high),
    (SERVE, _token_altered),
], ids=["state_unchanged", "half_batch", "lr_low", "b1_low", "b2_high", "token_altered"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, compile_cache, cell, fault):
    fault(monkeypatch)
    result = run(make_root(tmp_path), cell)
    assert not result["correct"], result["checks"]


def test_off_a_tpu_the_command_fails_and_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-mixtral-1chip", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == bench_run.EXIT_NO_CHIP, proc.stderr[-2000:]
    assert '"correct"' not in proc.stdout


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-mixtral-1chip", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
