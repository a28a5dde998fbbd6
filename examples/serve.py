"""Reconfigurable expert-parallel serving (DESIGN.md §9).

Drives a ServeEngine over a named workload mix: continuous batching with
(optionally chunked) prefill, decode-time gate-load monitoring into the
MixNet control plane, and live expert re-placement between ticks — then
proves the generation-consistency guarantee by replaying the identical
workload with reconfiguration off and comparing tokens bit-for-bit.

    PYTHONPATH=src python examples/serve.py [--arch grok-1-314b]
        [--mix chat|batch_summarize|agentic] [--requests 8]
        [--prefill-chunk 8] [--no-parity-check]
"""

import argparse
import dataclasses
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_model
from repro.obs import metrics, trace
from repro.parallel.sharding import make_plan
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.workload import MIXES, WorkloadGenerator


def build_engine(params, cfg, plan, args, reconfig: bool) -> ServeEngine:
    scfg = ServeConfig(
        slots=args.slots,
        max_len=args.max_len,
        prefill_chunk=args.prefill_chunk,
        reconfig_every=args.reconfig_every if reconfig else 0,
        reconfig_min_gain=0.0,
        num_devices=args.num_devices,
    )
    return ServeEngine(jax.tree.map(lambda a: a, params), cfg, plan, scfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="grok-1-314b")
    ap.add_argument("--mix", choices=sorted(MIXES), default="chat")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--reconfig-every", type=int, default=4)
    ap.add_argument("--num-devices", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-parity-check", action="store_true")
    ap.add_argument("--trace", default="",
                    help="export a Perfetto trace of the run to this path")
    ap.add_argument("--metrics", default="",
                    help="dump the metrics-registry snapshot to this path")
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace:
        trace.enable()

    cfg = get_reduced(args.arch)
    if cfg.encoder_layers:
        # The text workload generator cannot produce encoder frames; the
        # encoder-decoder serving shapes run through examples/quickstart +
        # the dry-run cells instead (DESIGN.md §4).
        raise SystemExit(
            f"{args.arch} is encoder-decoder (audio) — ServeEngine serves "
            "pure-decoder archs; pick a text arch"
        )
    if not cfg.is_moe:
        print(f"{args.arch} is dense — serving runs without a control plane")
    if cfg.is_moe and cfg.moe.num_experts % args.num_devices:
        args.num_devices = 1
    # Chunked prefill needs attention-only block patterns (DESIGN.md §9).
    if any(k not in ("global", "local") for k in (*cfg.block_pattern, *cfg.tail_pattern)):
        args.prefill_chunk = 0
    if args.max_len < 8:
        raise SystemExit("--max-len must be >= 8")
    plan = make_plan(None)
    params, _ = init_model(jax.random.PRNGKey(0), cfg, plan)

    gen = WorkloadGenerator(args.mix, seed=args.seed, vocab_size=cfg.vocab_size)
    out_cap = max(1, min(12, args.max_len // 4))
    reqs = [
        dataclasses.replace(
            r,
            # leave decode room: prompt + outputs must fit the slot cache
            prompt_len=max(1, min(r.prompt_len, args.max_len - out_cap - 2)),
            max_new_tokens=min(r.max_new_tokens, out_cap),
        )
        for r in gen.generate(args.requests)
    ]

    print(f"serving reduced {args.arch} ({cfg.family}) on mix={args.mix}: "
          f"{len(reqs)} requests, {args.slots} slots, prefill_chunk={args.prefill_chunk}")
    eng = build_engine(params, cfg, plan, args, reconfig=cfg.is_moe)
    rep = eng.run(reqs, gen)
    print(f"  completed={rep.completed}/{rep.requests} in {rep.ticks} ticks "
          f"({rep.wall_s:.1f}s wall, {rep.tokens_per_s:.1f} tok/s incl. compile)")
    print(f"  TTFT p50/p99 = {rep.ttft_ticks_p50:.0f}/{rep.ttft_ticks_p99:.0f} ticks; "
          f"TPOT = {rep.tpot_ticks_mean:.2f} ticks/token")
    print(f"  reconfigurations applied: {rep.reconfig_count} "
          f"(wire: {rep.wire_reconfig_count}); decode a2a bytes accounted: "
          f"{rep.a2a_bytes:.0f}")
    if rep.gate_load_total is not None:
        share = rep.gate_load_total.sum(0) / max(rep.gate_load_total.sum(), 1e-9)
        print(f"  gate-load share per expert: {np.round(share, 2)}")
    if eng.decision_log:
        print("  decision log (control-plane verdict each cadence):")
        for d in eng.decision_log:
            if d["kind"] == "reconfig":
                verdict = (
                    f"moved layers {d['layers']} (gain {d['gain_bytes']:.0f} B)"
                    if d["applied"] else f"held placement ({'; '.join(d['reasons'])})"
                )
                print(f"    tick {d['tick']:>4}: {verdict}")
            else:
                print(f"    tick {d['tick']:>4}: {d['kind']} "
                      f"{({k: v for k, v in d.items() if k not in ('tick', 'kind')})}")

    if cfg.is_moe and not args.no_parity_check:
        base = build_engine(params, cfg, plan, args, reconfig=False)
        base.run(reqs, gen)
        a = {r.rid: r.out for r in eng.batcher.finished}
        b = {r.rid: r.out for r in base.batcher.finished}
        assert a == b, "reconfiguration changed generated tokens"
        print("  parity: tokens bit-identical with reconfiguration off ✓")

    if args.trace:
        n = trace.export(args.trace)
        failures = trace.validate_file(args.trace)
        assert not failures, f"trace schema failures: {failures[:3]}"
        print(f"  trace: {n} events -> {args.trace} (schema OK; open in "
              "ui.perfetto.dev)")
    if args.metrics:
        metrics.default().to_json(args.metrics)
        print(f"  metrics snapshot -> {args.metrics}")


if __name__ == "__main__":
    main()
