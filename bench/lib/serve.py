"""Drives a serving cell: one ``ServeEngine`` under a closed loop of sessions.

Set-up builds the engine over the bench's weights, sends one short request
of every prompt length the mix can send (every program the window runs
compiles there), then starts every session at once and runs the loop until
that first burst has been admitted and ``fill_ticks`` engine ticks more.  The window
then runs the loop for ``--seconds``: after each engine tick the harness
stamps every token that reached the host, and a session whose request has
finished sends its next one.  After the window no request is sent; the loop
runs on until every request sent in the window has its first token.

Once the engine is freed, the reference reads a sample of the requests that
finished in the window, drawn from the seed with the longest among them.
"""

from __future__ import annotations

import gc

import numpy as np

from bench.lib import arch, clock, profile, program, weights
from bench.lib.record import Record, RequestLog, Tick
from bench.reference import checks

WARM_NEW_TOKENS = 2
DRAIN_LIMIT_S = 60.0


class Loop:
    """The sessions, the engine, and what the harness saw of each tick."""

    def __init__(self, engine, pool):
        self.engine = engine
        self.pool = pool
        self.next = 0
        self.live: dict[int, tuple[object, RequestLog]] = {}
        self.logs: list[RequestLog] = []
        self.ticks: list[Tick] = []
        self.sending = True

    def send(self) -> None:
        prompt, out = self.pool[self.next % len(self.pool)]
        rid = self.next
        self.next += 1
        req = program.request(rid, prompt, out)
        log = RequestLog(rid, len(prompt), out, clock.now())
        self.engine.submit(req)
        self.live[rid] = (req, log)
        self.logs.append(log)

    def tick(self) -> Tick:
        tick = Tick(self.engine.tick, clock.now(), 0.0)
        with profile.annotate("bench.tick"):
            self.engine.step()
        tick.end = end = clock.now()
        with profile.annotate("bench.sessions"):
            done = []
            for rid, (req, log) in self.live.items():
                before, after = len(log.token_times), len(req.out)
                decoded = after - before
                if after and not before:
                    # Admitted this tick: prefill gave the first token.
                    tick.admitted_prompt_tokens.append(log.prompt_len)
                    decoded -= 1
                if decoded > 0:
                    tick.live += 1
                    tick.context_tokens += log.prompt_len + after - 1
                log.token_times.extend([end] * (after - before))
                if req.finish_tick >= 0:
                    log.error = req.error
                    log.tokens = list(req.out)
                    done.append(rid)
            for rid in done:
                del self.live[rid]
                if self.sending:
                    self.send()
        self.ticks.append(tick)
        return tick


def far_share(gaps: list[float], far: float) -> float:
    """Share of served tokens whose reference logit lies more than ``far``
    below the reference's best at that position."""
    return sum(g > far for g in gaps) / len(gaps)


def gap_summary(gaps: list[float]) -> dict:
    return {"tokens": len(gaps), "widest": max(gaps),
            **{f"share_over_{t}": far_share(gaps, t) for t in (0.0, 0.03, 0.1, 0.3, 1.0)}}


def sample_finished(logs: list[RequestLog], t0: float, t1: float, tokens: int,
                    seed: int) -> list[RequestLog]:
    """Requests that finished in ``(t0, t1]``: the longest, then others in a
    seeded order until ``tokens`` served tokens are in the sample."""
    done = [g for g in logs if g.error is None and g.tokens and t0 < g.token_times[-1] <= t1]
    if not done:
        return []
    done.sort(key=lambda g: -len(g.tokens))
    rest = done[1:]
    order = np.random.default_rng(int(seed)).permutation(len(rest))
    sample, total = [done[0]], len(done[0].tokens)
    for i in order:
        if total >= tokens:
            break
        sample.append(rest[i])
        total += len(rest[i].tokens)
    return sample


def run(ctx) -> Record:
    cell, seed = ctx.cell, ctx.seed
    c, t = cell.config, cell.traffic
    gen = ctx.traffic
    model = arch.of(c, cell.root)
    engine = program.serve_engine(model, c, weights.make(seed, c, model), slots=t["slots"],
                                  max_len=gen.max_len(t))
    pool = gen.requests(t, c, seed)
    loop = Loop(engine, pool)

    # Warm every prompt length and the decode step.
    rng = np.random.default_rng(int(seed) + 1)
    for n in gen.prompt_lengths(t):
        engine.submit(program.request(-n, rng.integers(0, c["vocab_size"], n, dtype=np.int32),
                                      WARM_NEW_TOKENS))
    while engine.batcher.busy:
        engine.step()

    # Fill: every session sends, the burst is admitted, the loop settles.
    for _ in range(int(t["sessions"])):
        loop.send()
    while any(not g.token_times for _, g in loop.live.values()):
        loop.tick()
    for _ in range(int(t["fill_ticks"])):
        loop.tick()

    # The window.
    tracer = program.program_spans()
    seconds = min(ctx.seconds, t["trace_s"]) if ctx.trace else ctx.seconds
    if ctx.trace:
        tracer.enable().clear()
    lowered, setup_compile_s = ctx.clock.lowerings, ctx.clock.compile_s
    first_tick = len(loop.ticks)
    with ctx.capture(), profile.annotate(profile.WINDOW):
        w0 = clock.now()
        while clock.now() - w0 < seconds:
            loop.tick()
    w1 = loop.ticks[-1].end
    spans = [e for e in tracer.default().events() if e.get("ph") == "X"] if ctx.trace else None
    tracer.disable()
    loop.sending = False
    in_window = [g for g in loop.logs if w0 <= g.send < w1]
    d0 = clock.now()
    while any(not g.token_times and g.error is None for g in in_window) \
            and clock.now() - d0 < DRAIN_LIMIT_S:
        loop.tick()

    rec = Record(c, t, ctx.peaks, cell.chips, (w0, w1), ctx.age_at(w0))
    rec.ticks = loop.ticks[first_tick:]
    rec.requests = loop.logs
    rec.spans = spans
    rec.attempted = len(in_window)
    rec.failed = sum(1 for g in in_window if g.error is not None or not g.token_times)
    window_ticks = [k for k in rec.ticks if k.end <= w1]
    rec.counts["setup_backend_compile_s"] = setup_compile_s
    rec.counts["compiles_in_window"] = ctx.clock.lowerings - lowered
    rec.counts["ticks_in_window"] = len(window_ticks)
    rec.counts["admissions_in_window"] = sum(len(k.admitted_prompt_tokens) for k in window_ticks)
    rec.counts["requests_sent_in_window"] = len(in_window)
    # How much of the K/V pool (slots x longest context) the decoding slots
    # attend over, per tick.
    pool_tokens = int(t["slots"]) * gen.max_len(t)
    held = [k.context_tokens / pool_tokens for k in window_ticks]
    if held:
        rec.counts["kv_pool_share_mean"] = float(np.mean(held))
        rec.counts["kv_pool_share_max"] = float(np.max(held))
    rec.memory_peak_bytes = ctx.memory_peak()

    limits = cell.limits
    sample = sample_finished(loop.logs, w0, w1, int(limits["sample_tokens"]), seed)
    rec.counts["sampled_requests"] = len(sample)
    rec.counts["sampled_tokens"] = sum(len(g.tokens) for g in sample)
    pairs = [(pool[g.rid % len(pool)][0], g.tokens) for g in sample]

    # Free the engine, then the reference reads the sample.
    del engine, loop
    gc.collect()
    w = weights.make(seed, c, model)
    ref = arch.reference(c, cell.root)
    view, pad_to, far = model.reference_view(c), int(limits["pad_to"]), float(limits["far_gap"])
    gaps = checks.serve_gaps(ref, w, pairs, view, pad_to=pad_to)
    if gaps:
        rec.checks = {"far_token_share": far_share(gaps, far)}
        rec.counts["widest_logit_gap"] = max(gaps)
    if ctx.control and pairs:
        fp8 = checks.serve_gaps(ref, w, pairs, view, pad_to=pad_to, precision="fp8")
        rec.counts["control_fp8"] = gap_summary(fp8)
        rec.counts["program"] = gap_summary(gaps)
    return rec
