"""``moe_dataplane.device_ms_per_step.train``: device self-time of the
operations the program names ``moe.route``, ``moe.dispatch`` and
``moe.combine`` (``jax.named_scope`` in ``models/moe.py``: forward,
recomputed and backward; the expert GEMMs under ``moe.experts`` are not
counted), in ms per training step of the traced window.  Device trace;
``bench/lib/program_trace.py``."""

import pathlib

from bench.lib import program_trace

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(rec):
    pt = program_trace.of_record(rec, ROOT)
    steps = rec.steps_in_window()
    if pt is None or not steps:
        return None
    secs = pt.scope_seconds()
    if not any(s in secs for s in program_trace.DATAPLANE):
        return None
    return 1000.0 * sum(secs.get(s, 0.0) for s in program_trace.DATAPLANE) / len(steps)
