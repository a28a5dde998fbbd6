"""Smoke run of MixNet's two main paths on a TPU, at Mixtral-8x7B widths.

    python chip_smoke.py              # one chip: kernel checks, serve, train
    python chip_smoke.py --four-chip  # four chips: expert-parallel training
                                      # with live re-placement, against the
                                      # same run with re-placement off

Widths are Mixtral-8x7B's published ones (``MIXTRAL_8X7B_CONFIG``: d_model
4096, 32 query and 8 KV heads of 128, expert d_ff 14336, top-2 routing, vocab
32000, bf16).  Depth and the experts held are cut to fit the chips; every cut
is printed before the phase it applies to, and no width is ever cut.  Weights
and data are random from ``--seed``.

The script runs in one process (a chip belongs to one process) and goes
through the normal entry points, ``Trainer`` and ``ServeEngine``.  It exits
nonzero, without printing the final JSON line, when JAX finds no TPU or any
phase fails.  The JAX compile cache lives where
``repro.launch.compile_cache.enable_compile_cache`` puts it.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_models import MIXTRAL_8X7B_CONFIG
from repro.data.pipeline import SyntheticLM
from repro.kernels import ops, ref
from repro.kernels.grouped_matmul import pick_tiles
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.transformer import init_model
from repro.obs import trace
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.workload import WorkloadGenerator, clamp_requests
from repro.train.trainer import Trainer, TrainerConfig

# Train phase: one chip holds 1 MoE layer with 4 of the 8 experts (router
# over 4) and bf16 Adam moments: ~1.0 B parameters, ~8 GB of parameters,
# gradients and moments.  Sequences of 1024 keep the 128-row flash tiles whole.
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 1024, 4, 8
# Serve phase: 2 layers with all 8 experts (~3.2 B parameters, ~6.3 GB bf16)
# plus the paged KV pool.
SERVE_LAYERS, SERVE_REQUESTS, SERVE_SLOTS, SERVE_MAX_LEN = 2, 4, 4, 128
SERVE_MAX_NEW = 16
# Four-chip phase: 2 layers, all 8 experts over a (data=1, model=4) mesh.
FOUR_LAYERS, FOUR_STEPS, FOUR_RECONFIG_EVERY = 2, 6, 2
# Re-placement only renames expert slots (weights and Adam moments move
# together); the run differs from the one without it only in the summation
# order of the expert weight gradients, which can flip the bf16 rounding of
# a few updated weights: far below 1e-3 of a loss near 10.
FOUR_LOSS_TOL = 1e-3


def train_config(num_layers: int, num_experts: int):
    base = MIXTRAL_8X7B_CONFIG
    return dataclasses.replace(
        base,
        num_layers=num_layers,
        opt_moment_dtype="bfloat16",
        moe=dataclasses.replace(
            base.moe, num_experts=num_experts, backend="mixnet",
            dispatch="dropless",
        ),
    )


def serve_config():
    base = MIXTRAL_8X7B_CONFIG
    return dataclasses.replace(
        base,
        num_layers=SERVE_LAYERS,
        moe=dataclasses.replace(base.moe, backend="mixnet"),
    )


def optimizer(cfg, steps: int) -> AdamWConfig:
    # Adam's first updates move every weight by about lr at once.  At
    # d_model 4096 lr 1e-3 overshoots (the loss rose from 10.9 to 12.5 over 8
    # steps on a v5e); 1e-4 keeps those first steps small.
    return AdamWConfig(
        lr=1e-4, warmup_steps=2, total_steps=10 * steps,
        moment_dtype=cfg.opt_moment_dtype,
    )


class CompileClock:
    """Sums XLA backend-compile seconds reported through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Kernels against their ref.py oracles
# ---------------------------------------------------------------------------


def _compare(name: str, got, want, rel_tol: float) -> None:
    """max|got - want| over max|want| must not exceed ``rel_tol``."""
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape} or non-finite")
    rel = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    say(f"  check {name}: rel err {rel!r} (tol {rel_tol!r})")
    if rel > rel_tol:
        raise AssertionError(f"{name}: rel err {rel} > {rel_tol}")


def kernel_checks(tcfg, scfg, seed: int) -> None:
    """Each kernel on the train and serve paths, once, at the smoke's shapes,
    through ``ops`` (the path the models take) against its ``ref.py``
    oracle evaluated at full f32 matmul precision.

    Tolerances: dispatch is a row copy (exact); combine sums two f32 terms;
    the bf16-output kernels (grouped GEMM, flash and paged attention) round
    their result to bf16 (2^-8 relative) after an f32 accumulation in another
    order than the oracle's, so they get 2^-6 of the output's range; the
    router's f32 softmax gets 1e-6 and identical expert ids."""
    d, f = tcfg.d_model, tcfg.moe.d_ff
    e, k = tcfg.moe.num_experts, tcfg.moe.top_k
    hq, hkv, dh = tcfg.num_heads, tcfg.num_kv_heads, tcfg.resolved_head_dim
    t = TRAIN_BATCH * TRAIN_SEQ
    blk = pick_tiles(t * k, e, d, f)[0]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    bf16 = jnp.bfloat16
    hi = jax.default_matmul_precision("highest")

    x = jax.random.normal(next(keys), (t, d), bf16)
    src = jax.random.randint(next(keys), (t * k,), -1, t)
    got = ops.moe_dispatch(x, src)
    with hi:
        _compare("moe_dispatch", got, ref.moe_dispatch(x, src), 0.0)

    y = jax.random.normal(next(keys), (t * k, d), bf16)
    slot = jax.random.randint(next(keys), (t, k), -1, t * k)
    w = jax.random.uniform(next(keys), (t, k), jnp.float32)
    got = ops.moe_combine(y, slot, w)
    with hi:
        _compare("moe_combine", got, ref.moe_combine(y, slot, w), 1e-6)

    n = t * k // blk + e
    xb = jax.random.normal(next(keys), (n, blk, d), bf16)
    wg = (jax.random.normal(next(keys), (e, d, f), jnp.float32) * d**-0.5).astype(bf16)
    be = jnp.sort(jax.random.randint(next(keys), (n,), 0, e))
    used = jnp.int32(n - e)  # the last e tiles are skipped, as padding is
    got = ops.grouped_matmul(xb, wg, block_experts=be, used_blocks=used)
    with hi:
        _compare("grouped_matmul_blocks", got,
                 ref.grouped_matmul_blocks(xb, wg, be, used), 2.0**-6)
    del xb, wg

    logits = jax.random.normal(next(keys), (t, e), jnp.float32)
    gw, gi = ops.topk_gating(logits, k)
    rw, ri = ref.topk_gating(logits, k)
    _compare("topk_gating weights", gw, rw, 1e-6)
    if not bool(jnp.all(gi == ri)):
        raise AssertionError("topk_gating: expert ids differ from the oracle")

    q = jax.random.normal(next(keys), (TRAIN_BATCH, hq, TRAIN_SEQ, dh), bf16)
    kk = jax.random.normal(next(keys), (TRAIN_BATCH, hkv, TRAIN_SEQ, dh), bf16)
    vv = jax.random.normal(next(keys), (TRAIN_BATCH, hkv, TRAIN_SEQ, dh), bf16)
    got = ops.flash_attention(q, kk, vv)
    with hi:
        _compare("flash_attention", got, ref.flash_attention(q, kk, vv), 2.0**-6)
    del q, kk, vv

    page, pages = ServeConfig.page_size, SERVE_MAX_LEN // ServeConfig.page_size
    n_pool = SERVE_SLOTS * pages
    qd = jax.random.normal(next(keys), (SERVE_SLOTS, 1, scfg.num_heads, dh), bf16)
    kp = jax.random.normal(next(keys), (n_pool, page, scfg.num_kv_heads, dh), bf16)
    vp = jax.random.normal(next(keys), (n_pool, page, scfg.num_kv_heads, dh), bf16)
    table = jax.random.permutation(next(keys), n_pool).reshape(SERVE_SLOTS, pages)
    lengths = jax.random.randint(next(keys), (SERVE_SLOTS,), 0, SERVE_MAX_LEN)
    got = ops.paged_flash_decode(qd, kp, vp, table, lengths)
    with hi:
        _compare("paged_flash_decode", got,
                 ref.paged_flash_decode(qd, kp, vp, table, lengths), 2.0**-6)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def serve_phase(cfg, seed: int, clock: CompileClock) -> None:
    plan = make_plan(None)
    params, _ = init_model(jax.random.PRNGKey(seed), cfg, plan)
    gen = WorkloadGenerator("chat", seed=seed, vocab_size=cfg.vocab_size)
    reqs = clamp_requests(gen.generate(SERVE_REQUESTS), max_new=SERVE_MAX_NEW)
    scfg = ServeConfig(slots=SERVE_SLOTS, max_len=SERVE_MAX_LEN, paged=True)
    say(f"  requests: chat mix, prompt lengths {[r.prompt_len for r in reqs]}, "
        f"{SERVE_MAX_NEW} new tokens each")

    cold = ServeEngine(params, cfg, plan, scfg)
    rep = cold.run(reqs, gen)
    say(f"  cold run: {rep.completed}/{rep.requests} requests, {rep.tokens_out} "
        f"tokens served, {rep.wall_s!r} s wall, compile {clock.lap()!r} s")

    # Warm run of the same stream, traced: per-tick host spans, each closed
    # after the tick's tokens reached the host.
    tracer = trace.enable()
    tracer.clear()
    warm = ServeEngine(params, cfg, plan, scfg)
    rep_w = warm.run(reqs, gen)
    ticks = [ev for ev in tracer.events() if ev["name"] == "serve.tick"]
    trace.disable()
    decode = [ev["dur"] * 1e-6 for ev in ticks if ev["args"].get("admitted") == 0]
    say(f"  warm run: {len(ticks)} ticks, {rep_w.wall_s!r} s wall, steady decode "
        f"tick median {float(np.median(decode))!r} s over {len(decode)} ticks, "
        f"compile {clock.lap()!r} s")

    toks_cold = {r.rid: r.out for r in cold.batcher.finished}
    toks_warm = {r.rid: r.out for r in warm.batcher.finished}
    served = sum(len(v) for v in toks_cold.values())
    if rep.completed != len(reqs) or served != SERVE_MAX_NEW * len(reqs):
        raise AssertionError(f"served {served} tokens for {rep.completed} requests")
    if any(not 0 <= t < cfg.vocab_size for v in toks_cold.values() for t in v):
        raise AssertionError("a served token is outside the vocabulary")
    if toks_cold != toks_warm:
        raise AssertionError("the warm run generated different tokens")
    say(f"  tokens served {served}; warm run reproduces the cold run's tokens; "
        f"peak_bytes_in_use {peak_bytes()}")


def train_phase(cfg, seed: int, clock: CompileClock) -> None:
    plan = make_plan(None)
    opt = optimizer(cfg, TRAIN_STEPS)
    trainer = Trainer(
        cfg, opt, TrainerConfig(total_steps=TRAIN_STEPS), plan, seed=seed
    )
    say(f"  {sum(a.size for a in jax.tree.leaves(trainer.params))} parameters, "
        f"batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, init compile {clock.lap()!r} s")
    log = trainer.train(iter(SyntheticLM(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH, seed=seed)))
    losses = [float(m["loss"]) for m in log]
    times = [float(m["step_time_s"]) for m in log]
    say(f"  losses {losses}")
    say(f"  step compile {clock.lap()!r} s, first step {times[0]!r} s, steady step "
        f"median {float(np.median(times[1:]))!r} s, peak_bytes_in_use {peak_bytes()}")
    if not all(np.isfinite(losses)):
        raise AssertionError("non-finite training loss")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> {losses[-1]}")


def expert_bytes_per_device(params) -> dict[int, int]:
    out: dict[int, int] = {}
    for block in params["blocks"].values():
        for name in ("w_in", "w_gate", "w_out"):
            for shard in block["moe"][name].addressable_shards:
                out[shard.device.id] = out.get(shard.device.id, 0) + shard.data.nbytes
    return dict(sorted(out.items()))


def four_chip_phase(seed: int, clock: CompileClock) -> None:
    cfg = train_config(FOUR_LAYERS, MIXTRAL_8X7B_CONFIG.moe.num_experts)
    mesh = make_mesh((1, 4), ("data", "model"))
    plan = make_plan(mesh)
    opt = optimizer(cfg, FOUR_STEPS)
    say(f"== four-chip expert parallelism: {cfg.num_layers} layers, "
        f"{cfg.moe.num_experts} experts over model=4 (2 per chip), bf16 moments, "
        f"batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, {FOUR_STEPS} steps")

    def run(reconfig_every: int):
        tcfg = TrainerConfig(
            total_steps=FOUR_STEPS, reconfig_every=reconfig_every,
            reconfig_min_gain=0.0,
        )
        with jax.set_mesh(mesh):
            trainer = Trainer(cfg, opt, tcfg, plan, mesh=mesh, seed=seed)
            log = trainer.train(
                iter(SyntheticLM(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH, seed=seed))
            )
        losses = [float(m["loss"]) for m in log]
        times = [float(m["step_time_s"]) for m in log]
        say(f"  reconfig_every={reconfig_every}: losses {losses}")
        say(f"    compile {clock.lap()!r} s, first step {times[0]!r} s, steady "
            f"step median {float(np.median(times[1:]))!r} s, reconfigs="
            f"{trainer.reconfig_count} (wire {trainer.wire_reconfig_count})")
        say(f"    expert bytes per device {expert_bytes_per_device(trainer.params)}")
        return trainer, losses

    trainer, moved = run(FOUR_RECONFIG_EVERY)
    reconfigs = trainer.reconfig_count
    per_dev = expert_bytes_per_device(trainer.params)
    del trainer
    gc.collect()
    _, fixed = run(0)
    diff = max(abs(a - b) for a, b in zip(moved, fixed))
    say(f"  reconfigs={reconfigs}; max |loss diff| vs re-placement off {diff!r} "
        f"(tol {FOUR_LOSS_TOL!r}); peak_bytes_in_use {peak_bytes()} on device 0")
    if reconfigs <= 0:
        raise AssertionError("no re-placement fired")
    if len(per_dev) != 4 or len(set(per_dev.values())) != 1:
        raise AssertionError(f"experts not spread evenly over 4 devices: {per_dev}")
    if not all(np.isfinite(moved + fixed)) or diff > FOUR_LOSS_TOL:
        raise AssertionError("losses with re-placement differ from without")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip expert-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache_dir = enable_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} chips, JAX found {len(devices)}")
    say(f"device: {dev.device_kind} x {len(devices)}; compile cache {cache_dir}")
    clock = CompileClock()

    if args.four_chip:
        four_chip_phase(args.seed, clock)
    else:
        tcfg = train_config(1, 4)
        scfg = serve_config()
        say("== kernels vs ref.py oracles (Pallas on the chip)")
        kernel_checks(tcfg, scfg, args.seed)
        say(f"  compile {clock.lap()!r} s, peak_bytes_in_use {peak_bytes()}")
        say(f"== serve: ServeEngine, cut: num_layers 32 -> {scfg.num_layers} "
            f"(all 8 experts), max_new_tokens <= {SERVE_MAX_NEW}; prefill through "
            f"mixnet, decode through the paged Pallas kernel")
        serve_phase(scfg, args.seed, clock)
        gc.collect()
        say(f"== train: Trainer, cut: num_layers 32 -> {tcfg.num_layers}, experts "
            f"8 -> {tcfg.moe.num_experts} (router over {tcfg.moe.num_experts}), "
            f"Adam moments float32 -> bfloat16; mixnet dropless")
        train_phase(tcfg, args.seed, clock)

    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))


if __name__ == "__main__":
    main()
