"""The program's own names in a profiler trace: its spans and its scopes.

The program's tracer (``repro.obs.trace``) mirrors each of its spans into
the profiler as a ``TraceAnnotation`` of the same name, and its compiled
code names each layer's device operations with ``jax.named_scope``
(``moe.route``, ``moe.dispatch``, ...), which the TPU trace carries as the
operation's ``tf_op``.  This module reads both from the files a ``--trace 1``
run leaves under ``.bench_traces/<cell>/``, into the flat event list of
``bench/lib/profile.py`` with two additions:

* ``host`` events also for the program's spans (names starting with one of
  ``PROGRAM_PREFIXES``), so the innermost host span at a time names what
  the program was doing there, where ``profile.Reduction`` sees only the
  harness's ``bench.*`` spans;
* every ``op`` event carries ``scope``: the innermost of ``SCOPES`` named in
  its ``tf_op``, or None.

The ``.xplane.pb`` gives the events as ``profile.load_events`` reads them, so
busy time and operation times come out the same; the ``tf_op`` of each
operation comes from the ``.trace.json.gz`` that the profiler writes beside
it, matched by device, HLO instruction and start.  A trace of a program that
has neither spans nor scopes reads with no program span and every scope None.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

from bench.lib import profile

PROGRAM_PREFIXES = ("serve.", "train.", "fleet.")
SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "attn", "kv.write", "lm_head", "loss")
# The MoE data plane without its expert GEMMs.
DATAPLANE = ("moe.route", "moe.dispatch", "moe.combine")
UNSCOPED = ""
NO_SPAN = "no harness span"  # what ``profile.Reduction.host_at`` says there

_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(re.escape(s) for s in SCOPES) + r")(?=$|[/):])")


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def scope_of(op_name: str | None) -> str | None:
    """The innermost program scope in an operation's name path, or None."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def _instruction(name: str) -> str:
    """``"%fusion.5 = bf16[...] fusion(...)"`` -> ``"fusion.5"``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _op_scopes(path: str) -> dict[tuple, str]:
    """``(device, instruction, start ns) -> scope`` for every scoped device
    operation of a profiler ``.trace.json.gz``."""
    with gzip.open(path, "rt") as f:
        events = json.load(f).get("traceEvents", [])
    devices, ops_lines = {}, set()
    for e in events:
        if e.get("ph") != "M":
            continue
        name = e.get("args", {}).get("name", "")
        if e.get("name") == "process_name" and name.startswith(profile.DEVICE_PLANE):
            devices[e["pid"]] = int(name[len(profile.DEVICE_PLANE):].split()[0])
        elif e.get("name") == "thread_name" and name == profile.OPS_LINE:
            ops_lines.add((e["pid"], e["tid"]))
    out = {}
    for e in events:
        if e.get("ph") != "X" or (e.get("pid"), e.get("tid")) not in ops_lines:
            continue
        scope = scope_of(e.get("args", {}).get("tf_op"))
        if scope is not None and e["pid"] in devices:
            # The JSON's microseconds carry the device's picoseconds; the
            # .xplane.pb rounds them down to nanoseconds.
            start = int(float(e["ts"]) * 1000 + 1e-3)
            out[(devices[e["pid"]], e["name"], start)] = scope
    return out


def load_events(trace_dir: str) -> list[dict]:
    """The newest trace under ``trace_dir`` as ``profile.load_events`` reads
    it, plus the program's spans and each operation's ``scope``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    beside = glob.glob(os.path.join(os.path.dirname(files[-1]), "*.trace.json.gz"))
    scopes = _op_scopes(beside[0]) if beside else {}
    events = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith(profile.DEVICE_PLANE):
            device = int(plane.name[len(profile.DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name != profile.OPS_LINE:
                    continue
                for e in line.events:
                    start = int(e.start_ns)
                    events.append({"kind": "op", "device": device, "name": e.name,
                                   "start": float(e.start_ns), "dur": float(e.duration_ns),
                                   "scope": scopes.get((device, _instruction(e.name), start))})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(profile.HOST_PREFIX) or is_program_span(e.name):
                        events.append({"kind": "host", "device": None, "name": e.name,
                                       "start": float(e.start_ns),
                                       "dur": float(e.duration_ns)})
    return events


def innermost_segments(host: list[tuple[str, float, float]]) -> list[tuple[float, float, str]]:
    """The host timeline cut wherever a span opens or closes, each piece
    ``(start, end, name)`` named by the shortest span that covers it."""
    cuts = sorted({t for _, s, e in host for t in (s, e)})
    by_start = sorted(host, key=lambda h: h[1])
    active: list[tuple[str, float, float]] = []
    out = []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(by_start) and by_start[j][1] <= a:
            active.append(by_start[j])
            j += 1
        active = [h for h in active if h[2] > a]
        if active:
            out.append((a, b, min(active, key=lambda h: h[2] - h[1])[0]))
    return out


class ProgramTrace:
    """One traced window read with the program's names."""

    def __init__(self, events: list[dict], window: tuple[float, float] | None = None):
        # Program spans are host events like the harness's, so the
        # reduction's innermost span at a time may now be one of them.
        self.red = profile.Reduction(events, window)
        ops = [dict(e, name=e.get("scope") or UNSCOPED) if e["kind"] == "op" else e
               for e in events]
        self.scopes = profile.Reduction(ops, (self.red.t0, self.red.t1))

    def scope_seconds(self) -> dict[str, float]:
        """Device self-seconds per program scope (``UNSCOPED`` for the
        rest), averaged over the chips."""
        return self.scopes.op_seconds()

    def scoped_busy_share(self) -> float | None:
        secs = self.scope_seconds()
        total = sum(secs.values())
        return 1.0 - secs.get(UNSCOPED, 0.0) / total if total > 0 else None

    def span_count(self, name: str) -> int:
        """Host spans ``name`` that start inside the window."""
        return sum(1 for n, s, _ in self.red.host if n == name and s >= self.red.t0)

    def idle_by_span(self) -> dict[str, float]:
        """Device-idle seconds of the window by the innermost host span
        (program or harness) the host was in, averaged over the chips."""
        segs = innermost_segments(self.red.host)
        starts = [s for s, _, _ in segs]
        out: dict[str, float] = {}
        for _, g0, g1 in self.red.gaps():
            covered = 0.0
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(segs) and segs[i][0] < g1:
                s, e, name = segs[i]
                part = min(e, g1) - max(s, g0)
                if part > 0:
                    out[name] = out.get(name, 0.0) + part
                    covered += part
                i += 1
            if g1 - g0 - covered > 0:
                out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (g1 - g0 - covered)
        n = max(len(self.red.devices), 1)
        return {k: v * 1e-9 / n for k, v in out.items()}

    def counts(self) -> dict:
        """What a run prints of this trace beside its metrics."""
        top = self.red.breakdown()["idle_gaps"]
        return {
            "scoped_busy_share": self.scoped_busy_share(),
            "device_s_by_scope": self.scope_seconds(),
            "idle_s_by_span": self.idle_by_span(),
            "idle_gaps_by_program_span": top,
        }


_READ: dict[int, tuple[object, ProgramTrace | None]] = {}


def of_record(rec, root) -> ProgramTrace | None:
    """The traced window of run ``rec`` read with the program's names: the
    newest trace under ``<root>/.bench_traces`` whose window is the one
    ``rec.trace`` was reduced over (None without one).  Read once per run;
    the first read adds ``counts()`` to the run's counts."""
    if rec.trace is None:
        return None
    key = id(rec.trace)
    if key in _READ and _READ[key][0] is rec.trace:
        return _READ[key][1]
    files = sorted(glob.glob(os.path.join(str(root), ".bench_traces", "*", "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pt = None
    if files:
        events = load_events(os.path.dirname(files[-1]))
        try:
            window = profile.window_of(events)
        except ValueError:
            window = None
        if window == (rec.trace.t0, rec.trace.t1):
            pt = ProgramTrace(events, window)
            rec.counts.update(pt.counts())
    _READ[key] = (rec.trace, pt)
    return pt
