"""Profiler traces: capture a window, and reduce it to device busy time,
the operations that took it, and the idle gaps with what the host was doing.

A trace is read from JAX's ``.xplane.pb`` into a flat list of events, each a
dict ``{"kind": "op" | "host", "device": int | None, "name": str,
"start": ns, "dur": ns}``:

* ``op``   — one operation on a device: the events of the ``XLA Ops`` line
  of each ``/device:TPU:<n>`` plane;
* ``host`` — one of the harness's own ``jax.profiler.TraceAnnotation`` spans
  (names starting with ``bench.``) on any host thread.

The harness marks the measured window with a ``bench.window`` span; every
reduction is clipped to it.  The reduction works on that flat list alone, so
it can be checked on a recorded trace without a chip.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import shutil

HOST_PREFIX = "bench."
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"


@contextlib.contextmanager
def capture(trace_dir: str):
    """Trace everything inside the block into ``trace_dir`` (emptied first)."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """A host span in the profiler's own trace (no-op cost when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_events(trace_dir: str) -> list[dict]:
    """Flatten the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith(DEVICE_PLANE):
            device = int(plane.name[len(DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    events.append({"kind": "op", "device": device, "name": e.name,
                                   "start": float(e.start_ns), "dur": float(e.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        events.append({"kind": "host", "device": None, "name": e.name,
                                       "start": float(e.start_ns),
                                       "dur": float(e.duration_ns)})
    return events


def save_events(events: list[dict], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_saved(path: str) -> list[dict]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window_of(events: list[dict]) -> tuple[float, float]:
    wins = [e for e in events if e["kind"] == "host" and e["name"] == WINDOW]
    if not wins:
        raise ValueError("the trace holds no bench.window span")
    w = max(wins, key=lambda e: e["dur"])
    return w["start"], w["start"] + w["dur"]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, t0, t1):
    for e in events:
        s, end = max(e["start"], t0), min(e["start"] + e["dur"], t1)
        if end > s:
            yield e, s, end


class Reduction:
    """What one traced window says about the devices."""

    def __init__(self, events: list[dict], window: tuple[float, float] | None = None):
        self.t0, self.t1 = window if window is not None else window_of(events)
        self.window_s = (self.t1 - self.t0) * 1e-9
        ops = [e for e in events if e["kind"] == "op"]
        self.devices = sorted({e["device"] for e in ops})
        self.ops = {d: [] for d in self.devices}  # device -> [(name, start, end)]
        for e, s, end in _clip(ops, self.t0, self.t1):
            self.ops[e["device"]].append((e["name"], s, end))
        self.host = sorted(
            ((e["name"], s, end) for e, s, end in _clip(
                [e for e in events if e["kind"] == "host" and e["name"] != WINDOW],
                self.t0, self.t1)),
            key=lambda h: h[1],
        )
        self.busy = {d: union([(s, e) for _, s, e in self.ops[d]]) for d in self.devices}

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        total = sum(e - s for d in self.devices for s, e in self.busy[d])
        return total * 1e-9 / len(self.devices)

    def idle_share(self) -> float | None:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self) -> dict[str, float]:
        """Device self-seconds per operation name, averaged over the chips:
        an operation that holds others (a ``while`` loop around its body)
        counts only the time none of them ran; operations that merely
        overlap count in full."""
        out: dict[str, float] = {}
        for d in self.devices:
            stack: list[list] = []  # open [name, end, self time]
            for name, s, e in sorted(self.ops[d], key=lambda o: (o[1], -o[2])):
                while stack and (stack[-1][1] <= s or e > stack[-1][1]):
                    done = stack.pop()
                    out[done[0]] = out.get(done[0], 0.0) + done[2] * 1e-9
                if stack:
                    stack[-1][2] -= e - s
                stack.append([name, e, e - s])
            for name, _, own in stack:
                out[name] = out.get(name, 0.0) + own * 1e-9
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def gaps(self) -> list[tuple[int, float, float]]:
        """Idle ``(device, start, end)`` spans inside the window."""
        out = []
        for d in self.devices:
            t = self.t0
            for s, e in self.busy[d]:
                if s > t:
                    out.append((d, t, s))
                t = max(t, e)
            if self.t1 > t:
                out.append((d, t, self.t1))
        return out

    def host_at(self, t: float) -> str:
        """The innermost harness span covering host time ``t``."""
        best = None
        for name, s, e in self.host:
            if s <= t <= e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0] if best else "no harness span"

    def breakdown(self, top: int = 10) -> dict:
        """The ``top`` operations by device time, named by their HLO
        instruction (the trace's event name up to `` = ``), and the ``top``
        longest idle gaps, named by the harness span the host was in."""
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(), key=lambda g: -(g[2] - g[1]))[:top]
        return {
            "device_ops": [[name.split(" = ", 1)[0], sec] for name, sec in ops],
            "idle_gaps": [[self.host_at((s + e) / 2), (e - s) * 1e-9] for _, s, e in gaps],
        }
