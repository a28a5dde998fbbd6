"""The program's spans and scopes read from a profiler trace
(``bench/lib/program_trace.py``) and the metrics that read them."""

import pathlib

import pytest

from bench.lib import profile
from bench.tests.test_bench_trace import DATA, ev, sweep_busy_ns

ROOT = pathlib.Path(__file__).resolve().parents[2]


def op(name, start, dur, scope=None, device=0):
    return dict(ev("op", name, start, dur, device), scope=scope)


def metric(name):
    from bench.lib import spec

    return spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py", f"metric_{name}").read


def engine_tick_events():
    # One engine tick [0, 100) ns of the window [0, 120): the harness's
    # bench.tick [0, 100) holds the program's serve.tick [5, 95), which holds
    # serve.decode [5, 60) and serve.emit [60, 80); bench.sessions [100, 120).
    # The device runs [0, 50) (decode, scoped attn then moe.experts) and
    # [110, 115) (unscoped).  Idle: [50, 110) and [115, 120).
    return [
        ev("host", "bench.window", 0, 120),
        ev("host", "bench.tick", 0, 100),
        ev("host", "serve.tick", 5, 90),
        ev("host", "serve.decode", 5, 55),
        ev("host", "serve.emit", 60, 20),
        ev("host", "bench.sessions", 100, 20),
        op("attn_op", 0, 20, "attn"), op("gemm", 20, 30, "moe.experts"),
        op("copy", 110, 5),
    ]


def test_a_gap_inside_a_program_span_is_named_by_it():
    from bench.lib import program_trace

    pt = program_trace.ProgramTrace(engine_tick_events())
    # Idle [50, 110): serve.decode 10, serve.emit 20, serve.tick (not in a
    # finer span) 15, bench.tick 5, bench.sessions 10; [115, 120):
    # bench.sessions 5.
    assert pt.idle_by_span() == pytest.approx({
        "serve.decode": 10e-9, "serve.emit": 20e-9, "serve.tick": 15e-9,
        "bench.tick": 5e-9, "bench.sessions": 15e-9,
    })
    # The breakdown names each gap by the innermost span at its midpoint.
    assert pt.red.breakdown()["idle_gaps"] == [["serve.emit", pytest.approx(60e-9)],
                                                ["bench.sessions", pytest.approx(5e-9)]]
    assert pt.span_count("serve.tick") == 1
    assert pt.scope_seconds() == pytest.approx(
        {"attn": 20e-9, "moe.experts": 30e-9, program_trace.UNSCOPED: 5e-9})
    assert pt.scoped_busy_share() == pytest.approx(50 / 55)


def test_idle_outside_every_span_is_named_as_the_harness_names_it():
    from bench.lib import program_trace

    pt = program_trace.ProgramTrace([ev("host", "bench.window", 0, 10),
                                     ev("host", "serve.tick", 2, 3), op("x", 0, 1)])
    assert pt.idle_by_span() == pytest.approx({"serve.tick": 3e-9,
                                               program_trace.NO_SPAN: 6e-9})


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp(moe.experts)/while/body/dot_general", "moe.experts"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/"
     "moe.experts/jit(grouped_matmul)", "moe.experts"),
    ("jit(serve_step)/while/body/attn/kv.write/scatter:", "kv.write"),
    ("jit(train_step)/loss/lm_head/dot_general:", "lm_head"),
    ("jit(f)/moe.routed/x", None),
    ("jit(f)/attention/x", None),
    ("", None),
    (None, None),
])
def test_scope_is_the_innermost_program_scope(op_name, scope):
    from bench.lib import program_trace

    assert program_trace.scope_of(op_name) == scope


def test_scopes_are_matched_to_operations_by_device_instruction_and_start(tmp_path):
    import gzip
    import json

    from bench.lib import program_trace

    doc = {"traceEvents": [
        {"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name", "args": {"name": "XLA Modules"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 42279.731578, "dur": 0.02, "name": "fusion.3",
         "args": {"tf_op": "jit(f)/moe.dispatch/gather:"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 42279.7315, "dur": 15.4, "name": "while.4",
         "args": {"tf_op": "jit(f)/while:"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 42279.349, "dur": 0.38, "name": "copy.33",
         "args": {}},
        {"ph": "X", "pid": 3, "tid": 2, "ts": 42279.0, "dur": 36.4, "name": "jit_f(1)",
         "args": {"tf_op": "jit(f)/moe.route"}},
    ]}
    path = tmp_path / "host.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)
    assert program_trace._op_scopes(str(path)) == {(0, "fusion.3", 42279731): "moe.dispatch"}


def test_admit_reader_hand_counted():
    from bench.lib.record import Record

    rec = Record({}, {}, {}, 1, (0.0, 1.0), 0.0)
    rec.spans = [
        {"name": "serve.tick", "dur": 9000.0, "args": {"admitted": 2}},
        {"name": "serve.admit", "dur": 1000.0, "args": {"rid": 1, "tokens": 64}},
        {"name": "serve.admit", "dur": 3000.0, "args": {"rid": 2, "tokens": 192}},
    ]
    # 4 ms over 256 prompt tokens.
    assert metric("serve.admit_ms_per_ktok")(rec) == pytest.approx(15.625)
    rec.spans = [rec.spans[0]]
    assert metric("serve.admit_ms_per_ktok")(rec) is None


def test_engine_idle_and_dataplane_readers_hand_counted(monkeypatch):
    from bench.lib import program_trace
    from bench.lib.record import Record, Step

    rec = Record({}, {}, {}, 1, (0.0, 1.0), 0.0)
    events = engine_tick_events()
    rec.trace = profile.Reduction(events)
    monkeypatch.setattr(program_trace, "of_record",
                        lambda r, root: program_trace.ProgramTrace(events))
    # Idle in program spans: 10 + 20 + 15 ns over one tick.
    assert metric("serve.engine_idle_ms_per_tick")(rec) == pytest.approx(45e-6)
    # No data-plane scope in this trace: no reading.
    rec.steps = [Step(0.1, 0.2, 16, 1.0)]
    assert metric("moe_dataplane.device_ms_per_step.train")(rec) is None

    # Two steps; route 4 + dispatch 6 + combine 10 ns of self time (a
    # dispatch op inside a scoped loop counts once), experts left out.
    events = [ev("host", "bench.window", 0, 100),
              op("loop", 0, 30, "moe.dispatch"), op("inner", 5, 10, "moe.dispatch"),
              op("r", 30, 4, "moe.route"), op("g", 40, 30, "moe.experts"),
              op("c", 70, 10, "moe.combine"), op("other", 80, 10)]
    rec.steps = [Step(0.1, 0.2, 16, 1.0), Step(0.2, 0.3, 16, 1.0)]
    monkeypatch.setattr(program_trace, "of_record",
                        lambda r, root: program_trace.ProgramTrace(events))
    assert metric("moe_dataplane.device_ms_per_step.train")(rec) == pytest.approx(
        (30 + 4 + 10) * 1e-6 / 2)
    monkeypatch.setattr(program_trace, "of_record", lambda r, root: None)
    assert metric("serve.engine_idle_ms_per_tick")(rec) is None
    assert metric("moe_dataplane.device_ms_per_step.train")(rec) is None


def test_program_names_leave_every_existing_reading_unchanged():
    # The recorded trace of the parent program holds no program span or
    # scope: read with the program's names, busy time, operation times, the
    # breakdown and every existing trace metric come out the same.
    from bench.lib import program_trace, spec
    from bench.lib.record import Record

    events = profile.load_saved(DATA / "trace-serve-v5e.json.gz")
    plain = profile.Reduction(events)
    named = program_trace.ProgramTrace(events)
    assert named.red.busy_s() == plain.busy_s()
    assert named.red.op_seconds() == plain.op_seconds()
    assert named.red.breakdown() == plain.breakdown()
    assert named.scoped_busy_share() == 0.0
    assert named.span_count("serve.tick") == 0

    # The train cell's configuration: its model_type finds the counts.
    config = spec.read_json(ROOT / "bench" / "configs" / "mixtral-8x7b-train-1chip.json")

    def record(trace):
        rec = Record(config, {"seq_len": 4096, "batch": 1},
                     {"bf16_flops": 1.97e14, "hbm_bytes_per_s": 8.19e11}, 1, (0.0, 1.0), 0.0)
        rec.trace = trace
        return rec

    for name in ("device.idle_share.serve", "device.idle_share.train",
                 "moe_gemm.roofline_share.train"):
        assert metric(name)(record(plain)) == metric(name)(record(named.red))


def idle_inside(red, name):
    """Device-idle seconds inside the host spans ``name``, by intersection."""
    total = 0.0
    for _, g0, g1 in red.gaps():
        for n, s, e in red.host:
            if n == name:
                total += max(0.0, min(g1, e) - max(g0, s))
    return total * 1e-9


def test_recorded_v5e_serving_trace_with_program_spans(monkeypatch):
    # Three engine ticks of the serving cell's traced window (470 ms),
    # recorded on one TPU v5e with the program's spans mirrored into the
    # profiler and its scopes on the device operations; the first tick
    # admits two requests.
    from bench.lib import program_trace
    from bench.lib.record import Record

    events = profile.load_saved(DATA / "trace-serve-v5e-spans.json.gz")
    pt = program_trace.ProgramTrace(events)
    red = pt.red
    assert sum(1 for e in events if e["kind"] == "op") == 1547
    assert pt.span_count("serve.tick") == 3
    assert sum(1 for n, _, _ in red.host if n == "serve.admit") == 2
    assert red.busy_s() * 1e9 == pytest.approx(sweep_busy_ns(events, red.t0, red.t1))
    assert red.busy_s() == pytest.approx(profile.Reduction(events).busy_s())
    idle = pt.idle_by_span()
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s())
    # Innermost attribution agrees with plain intersection: what lies
    # inside the ticks is what the tick and its phases were given.
    phases = ("serve.admit", "serve.decode", "serve.emit", "serve.observe")
    assert sum(idle[n] for n in ("serve.tick",) + phases) == pytest.approx(
        idle_inside(red, "serve.tick"))
    for n in phases:
        assert idle[n] == pytest.approx(idle_inside(red, n))
    assert idle["serve.decode"] == pytest.approx(13.2141e-3, abs=1e-7)
    assert idle["serve.admit"] == pytest.approx(6.5735e-3, abs=1e-7)
    # More than 80% of the idle time is named by a span finer than the tick.
    finer = sum(idle[n] for n in phases + ("bench.sessions",))
    assert finer / sum(idle.values()) > 0.8
    # The paged decode kernel and the prefill's grouped GEMMs carry scopes.
    secs = pt.scope_seconds()
    assert secs["attn"] == pytest.approx(0.2108184, abs=1e-6)
    assert secs["moe.experts"] == pytest.approx(0.193718, abs=1e-6)
    assert sum(secs.values()) == pytest.approx(red.busy_s())
    assert pt.scoped_busy_share() == pytest.approx(0.908661, abs=1e-6)

    rec = Record({}, {}, {}, 1, (0.0, 1.0), 0.0)
    rec.trace = profile.Reduction(events)
    monkeypatch.setattr(program_trace, "of_record", lambda r, root: pt)
    engine_s = sum(v for n, v in idle.items() if program_trace.is_program_span(n))
    assert metric("serve.engine_idle_ms_per_tick")(rec) == pytest.approx(1e3 * engine_s / 3)
    assert metric("serve.engine_idle_ms_per_tick")(rec) == pytest.approx(7.1598, abs=1e-4)


def test_recorded_v5e_training_trace_with_scopes(monkeypatch):
    # One training step (2.5 s) of the training cell's traced window,
    # recorded on one TPU v5e with the program's scopes.
    from bench.lib import program_trace
    from bench.lib.record import Record, Step

    events = profile.load_saved(DATA / "trace-train-v5e-scopes.json.gz")
    pt = program_trace.ProgramTrace(events)
    plain = profile.Reduction(events)
    assert sum(1 for e in events if e["kind"] == "op") == 1012
    assert pt.red.op_seconds() == plain.op_seconds()
    assert pt.red.breakdown() == plain.breakdown()
    secs = pt.scope_seconds()
    assert sum(secs.values()) == pytest.approx(plain.busy_s())
    assert secs["moe.experts"] / plain.busy_s() > 0.9
    assert pt.scoped_busy_share() == pytest.approx(0.988641, abs=1e-6)

    rec = Record({}, {}, {}, 1, (0.0, 1.0), 0.0)
    rec.trace = plain
    rec.steps = [Step(0.2, 0.4, 4096, 10.0)]
    monkeypatch.setattr(program_trace, "of_record", lambda r, root: pt)
    # route 0.2827 + dispatch 11.6919 + combine 10.0186 ms in the one step.
    assert metric("moe_dataplane.device_ms_per_step.train")(rec) == pytest.approx(
        21.9932, abs=1e-3)
