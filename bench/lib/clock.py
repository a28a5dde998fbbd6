"""Host clocks of a run: when the process started, and what JAX compiled."""

from __future__ import annotations

import os
import time


def process_age_s() -> float:
    """Seconds since this process started, from ``/proc`` (Linux): the
    interpreter's own start-up counts toward set-up."""
    with open("/proc/self/stat") as f:
        # Field 22 (1-based), after the parenthesised command name.
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class CompileClock:
    """Counts what JAX compiles, through ``jax.monitoring``.

    Copied from the repository's ``chip_smoke.py`` ``CompileClock`` (backend
    compile seconds), with a count of lowerings added: every new program
    lowers once per process, whether its binary then comes from the
    persistent cache or from the compiler, so a lowering inside the measured
    window means the window met a shape set-up did not warm.
    """

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.lowerings = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == self.COMPILE:
            self.compile_s += duration
        elif event == self.LOWER:
            self.lowerings += 1


def now() -> float:
    return time.perf_counter()
