"""``serve.engine_idle_ms_per_tick``: the traced window's device-idle time
during which the host's innermost span was one of the program's own
(``serve.*``, mirrored into the profiler by ``repro.obs.trace``) and not
the harness's, in ms per engine tick (``serve.tick`` spans that start in
the window).  Device trace; ``bench/lib/program_trace.py``."""

import pathlib

from bench.lib import program_trace

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(rec):
    pt = program_trace.of_record(rec, ROOT)
    if pt is None or not pt.red.devices:
        return None
    ticks = pt.span_count("serve.tick")
    if not ticks:
        return None
    idle = sum(s for name, s in pt.idle_by_span().items() if program_trace.is_program_span(name))
    return 1000.0 * idle / ticks
