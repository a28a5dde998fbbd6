"""What one run recorded, as the metric readers see it."""

from __future__ import annotations

import dataclasses

from bench.lib.profile import Reduction


@dataclasses.dataclass
class Step:
    """One training step, on the host clock (``perf_counter`` seconds)."""

    start: float
    end: float
    tokens: int
    loss: float


@dataclasses.dataclass
class Tick:
    """One serving tick: the engine call and what the harness saw of it."""

    index: int  # the engine's tick counter before the call
    start: float
    end: float
    live: int = 0  # slots that decoded a token
    context_tokens: int = 0  # positions those slots attended over
    admitted_prompt_tokens: list[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class RequestLog:
    rid: int
    prompt_len: int
    out_len: int
    send: float
    token_times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] | None = None  # the served tokens, once finished
    error: str | None = None


@dataclasses.dataclass
class Record:
    config: dict
    traffic: dict
    peaks: dict
    chips: int
    window: tuple[float, float]  # host clock, the measured (or traced) window
    setup_s: float
    steps: list[Step] = dataclasses.field(default_factory=list)
    ticks: list[Tick] = dataclasses.field(default_factory=list)
    requests: list[RequestLog] = dataclasses.field(default_factory=list)
    trace: Reduction | None = None
    spans: list[dict] | None = None  # the program's own spans (repro.obs.trace)
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    checks: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int | None = None

    def steps_in_window(self) -> list[Step]:
        t0, t1 = self.window
        return [s for s in self.steps if s.start >= t0 and s.end <= t1]

    def ticks_in_window(self) -> list[Tick]:
        t0, t1 = self.window
        return [t for t in self.ticks if t.start >= t0 and t.end <= t1]
