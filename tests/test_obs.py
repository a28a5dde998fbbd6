"""Measurement plane (DESIGN.md §14): tracer schema + no-op disabled path,
metrics registry, traffic observatory math, typed decision events, and the
tracing-changes-nothing guarantee for serve and train."""

import dataclasses
import json
import time

import jax
import numpy as np
import pytest

from repro.core import commruntime as comm
from repro.models.config import ModelConfig, MoEConfig
from repro.models.transformer import init_model
from repro.obs import metrics, trace
from repro.obs.trace import Tracer, validate_events
from repro.obs.traffic import TrafficObservatory
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan
from repro.serve import events as sev
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.workload import WorkloadGenerator
from repro.train.trainer import Trainer, TrainerConfig

PLAN = make_plan(None)


@pytest.fixture(autouse=True)
def _clean_obs():
    """The default tracer/registry are process-wide; isolate each test."""
    trace.disable()
    trace.default().clear()
    metrics.reset()
    yield
    trace.disable()
    trace.default().clear()
    metrics.reset()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop():
    tr = Tracer()
    assert not tr.enabled
    # the disabled span is ONE shared object — no allocation on the hot path
    s1 = tr.span("a", x=1)
    s2 = tr.span("b")
    assert s1 is s2
    with s1 as sp:
        sp.set(y=2)
    tr.instant("i", k=1)
    tr.counter("c", 3.0)
    tr.audit("d", {"kind": "x"})
    assert tr.events() == []


def test_disabled_tracer_overhead_bounded():
    tr = Tracer()
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("hot"):
            pass
        tr.counter("c", 1.0)
        tr.instant("i")
    dt = time.perf_counter() - t0
    # ~3 attribute checks per iteration; generous bound for slow CI hosts.
    assert dt < 2.0, f"disabled tracer cost {dt / n * 1e6:.2f} us/iter"
    assert tr.events() == []


def test_span_counter_instant_schema_and_export(tmp_path):
    tr = Tracer()
    tr.enabled = True
    tid = tr.track("unit")
    with tr.span("outer", tid=tid, step=1) as sp:
        with tr.span("inner", tid=tid):
            pass
        sp.set(result=7)
    tr.counter("tokens", {"served": 3.0}, tid=tid)
    tr.instant("boom", tid=tid, cat="event", why="test")
    tr.audit("plan", {"layer": 0, "reconfigure": True}, tid=tid)
    evs = tr.events()
    assert [e["ph"] for e in evs] == ["X", "X", "C", "i", "i"]
    # spans record on exit: inner lands first, outer carries set() args
    assert evs[0]["name"] == "inner"
    assert evs[1]["args"] == {"step": 1, "result": 7}
    assert validate_events(evs) == []

    path = str(tmp_path / "t.json")
    n = tr.export(path)
    assert n == len(evs) + 2  # + process_name, + one thread_name
    doc = json.load(open(path))
    assert doc["traceEvents"][0]["ph"] == "M"
    names = {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert {"repro", "unit"} <= names
    assert trace.validate_file(path) == []


def test_validate_events_catches_malformed():
    ok = {"name": "a", "ph": "i", "s": "t", "ts": 0.0, "pid": 1, "tid": 1,
          "args": {}}
    assert validate_events([ok]) == []
    assert validate_events([{k: v for k, v in ok.items() if k != "ts"}])
    assert validate_events([dict(ok, ph="X")])  # span without dur
    assert validate_events([dict(ok, ph="C", args={"v": "str"})])
    assert validate_events([dict(ok, ph="?")])
    # partially overlapping spans on one track fail the nesting sweep
    a = dict(ok, ph="X", ts=0.0, dur=10.0)
    b = dict(ok, ph="X", name="b", ts=5.0, dur=10.0)
    assert any("overlap" in f for f in validate_events([a, b]))
    # properly nested spans (shared start) pass
    c = dict(ok, ph="X", name="c", ts=0.0, dur=4.0)
    assert validate_events([a, c]) == []


def test_tracer_ring_buffer_drops_oldest():
    tr = Tracer(capacity=8)
    tr.enabled = True
    for i in range(20):
        tr.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) <= 8
    assert evs[-1]["name"] == "e19"
    assert tr._dropped > 0


class _FakeAnnotation:
    """Records each enter and exit of a span's mirror."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_enabled_spans_mirror_into_one_annotation_each_in_nesting_order():
    _FakeAnnotation.log = []
    tr = Tracer()
    tr.bind_mirror(_FakeAnnotation)
    tr.enabled = True
    with tr.span("outer", step=1):
        with tr.span("inner", rid=7):
            pass
        with tr.span("second"):
            pass
    assert _FakeAnnotation.log == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
        ("enter", "second"), ("exit", "second"), ("exit", "outer"),
    ]
    # the tracer's own record is unchanged: args stay out of the mirror
    assert [e["name"] for e in tr.events()] == ["inner", "second", "outer"]
    assert tr.events()[0]["args"] == {"rid": 7}


def test_disabled_tracer_creates_no_mirror():
    _FakeAnnotation.log = []
    tr = Tracer()
    tr.bind_mirror(_FakeAnnotation)
    assert tr.span("a") is tr.span("b")
    with tr.span("a"):
        pass
    assert _FakeAnnotation.log == []
    assert tr.events() == []


def test_enable_binds_the_profiler_annotation():
    import jax.profiler

    tr = trace.enable()
    assert tr._mirror is jax.profiler.TraceAnnotation
    with tr.span("serve.tick", tick=0):
        pass
    assert [e["name"] for e in tr.events()] == ["serve.tick"]


def test_obs_imports_and_traces_without_jax():
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"
        "from repro import obs\n"
        "from repro.obs import trace\n"
        "tr = trace.enable()\n"
        "assert tr._mirror is None\n"
        "with tr.span('netsim.scenario'):\n"
        "    pass\n"
        "assert [e['name'] for e in tr.events()] == ['netsim.scenario']\n"
        "assert not any(m == 'jax' or m.startswith('jax.') for m in sys.modules\n"
        "               if sys.modules[m] is not None)\n"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env = dict(os.environ, PYTHONPATH=src)
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=120)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_series_and_snapshot():
    reg = metrics.MetricsRegistry()
    c = reg.counter("comm.link_bytes", op="a2a", link="scale_out")
    c.inc(10)
    c.inc(5)
    assert reg.counter("comm.link_bytes", op="a2a", link="scale_out") is c
    reg.gauge("loss").set(2.5)
    h = reg.histogram("lat_s")
    for v in (0.001, 0.002, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    key = "comm.link_bytes{link=scale_out,op=a2a}"
    assert snap["counters"][key]["value"] == 15.0
    assert snap["gauges"]["loss"]["value"] == 2.5
    assert snap["histograms"]["lat_s"]["count"] == 3
    assert snap["histograms"]["lat_s"]["max"] == 4.0
    assert reg.value("comm.link_bytes", op="a2a", link="scale_out") == 15.0
    assert reg.value("never.written") == 0.0
    json.dumps(snap)  # snapshot must be JSON-able
    with pytest.raises(TypeError):
        reg.gauge("comm.link_bytes", op="a2a", link="scale_out")


def test_metrics_reset_bumps_generation():
    reg = metrics.MetricsRegistry()
    g0 = reg.generation
    reg.counter("x").inc()
    reg.reset()
    assert reg.generation == g0 + 1
    assert reg.value("x") == 0.0


def test_commruntime_link_bytes_survive_registry_reset():
    from repro.core.fabric import FabricConfig, make_fabric

    fab = make_fabric("fat-tree", FabricConfig(num_servers=4, link_gbps=100))
    op = comm.AllToAll(comm.CommSpec.from_fabric(fab, 4))
    demand = np.full((4, 4), 1000.0)
    np.fill_diagonal(demand, 0.0)
    op.cost(fab, demand)
    before = metrics.default().value("comm.link_bytes", op="a2a", link="scale_out")
    assert before == pytest.approx(demand.sum())
    metrics.reset()
    op.cost(fab, demand)  # cached Counter handles must re-resolve, not orphan
    after = metrics.default().value("comm.link_bytes", op="a2a", link="scale_out")
    assert after == pytest.approx(before)


# ---------------------------------------------------------------------------
# traffic observatory
# ---------------------------------------------------------------------------


def test_observatory_locality_and_effective_experts():
    obs = TrafficObservatory(2, 4, num_devices=2)
    obs.record(np.array([[9.0, 0, 0, 0], [1.0, 1, 1, 1]]))
    loc = obs.locality_per_layer()
    assert loc[0] == pytest.approx(1.0)  # single expert takes everything
    assert loc[1] == pytest.approx(0.0)  # uniform
    eff = obs.effective_experts()
    assert eff[0] == pytest.approx(1.0)
    assert eff[1] == pytest.approx(4.0)
    # devices 0/1 hold experts {0,1}/{2,3}: layer 0 all on device 0
    conc = obs.device_concentration()
    assert conc[0] == pytest.approx(1.0)
    assert conc[1] == pytest.approx(0.5)
    assert 0.0 <= obs.locality_score() <= 1.0


def test_observatory_follows_permutation():
    obs = TrafficObservatory(1, 4, num_devices=2)
    load = np.array([[10.0, 0, 0, 0]])
    # expert 0 re-placed onto slot 3 (device 1)
    perm = np.array([[3, 1, 2, 0]])
    obs.record(load, perm)
    np.testing.assert_allclose(obs.device_traffic, [[0.0, 10.0]])


def test_observatory_regional_skew_and_roundtrip():
    obs = TrafficObservatory(1, 4, num_regions=2)
    # two disjoint half-regions: each misses the global (uniform) mix by
    # exactly 1 - sqrt(1/2)
    obs.record(np.array([[5.0, 5.0, 0, 0]]), region_weights={0: 1.0})
    obs.record(np.array([[0, 0, 5.0, 5.0]]), region_weights={1: 1.0})
    assert obs.regional_skew() == pytest.approx(1.0 - 2 ** -0.5)
    rep = obs.report()
    json.dumps(rep)
    back = TrafficObservatory.from_report(json.loads(json.dumps(rep)))
    assert back.ticks == 2
    np.testing.assert_allclose(back.expert_traffic, obs.expert_traffic)
    assert back.regional_skew() == pytest.approx(obs.regional_skew())
    assert back.report() == rep
    # merging two copies doubles mass, keeps the normalized stats
    merged = TrafficObservatory.from_report(rep).merge(back)
    assert merged.ticks == 4
    assert merged.locality_score() == pytest.approx(obs.locality_score())


def test_observatory_identical_regions_zero_skew():
    obs = TrafficObservatory(1, 4, num_regions=2)
    for r in (0, 1):
        obs.record(np.array([[4.0, 3.0, 2.0, 1.0]]), region_weights={r: 1.0})
    assert obs.regional_skew() == pytest.approx(0.0, abs=1e-9)


# ---------------------------------------------------------------------------
# typed decision events
# ---------------------------------------------------------------------------


def test_decision_events_match_legacy_dict_shape():
    # as_dict() must reproduce the legacy decision_log dicts exactly,
    # including key ORDER (consumers print the dicts verbatim).
    cases = [
        (sev.DrainDecision(tick=3, handed_back=2),
         ["tick", "kind", "handed_back"]),
        (sev.ReconfigDecision(tick=4, applied=True, layers=[0],
                              gain_bytes=9.0, reasons=[]),
         ["tick", "kind", "applied", "layers", "gain_bytes", "reasons"]),
        (sev.SteerDecision(tick=0, rid=1, region=2, slo="strict",
                           replica=0, reason="locality"),
         ["tick", "kind", "rid", "region", "slo", "replica", "reason"]),
        (sev.FleetFailDecision(tick=5, replica=1, resteered=3),
         ["tick", "kind", "replica", "resteered"]),
    ]
    for ev, keys in cases:
        d = ev.as_dict()
        assert list(d) == keys
        assert d["kind"] == ev.kind
        json.dumps(d)


# ---------------------------------------------------------------------------
# tracing changes nothing (serve + train)
# ---------------------------------------------------------------------------


def _moe_cfg():
    return ModelConfig(
        "obs", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=8, top_k=2, d_ff=32, capacity_factor=8.0,
                      backend="mixnet", a2a_group=2, dispatch="dropless"),
    )


def _run_serve(params, cfg, reqs, gen):
    scfg = ServeConfig(slots=2, max_len=40, reconfig_every=3,
                       reconfig_min_gain=0.0, num_devices=4)
    eng = ServeEngine(jax.tree.map(lambda a: a, params), cfg, PLAN, scfg)
    eng.run(reqs, gen)
    return eng


def test_serve_bit_identical_with_tracing_and_trace_contents(tmp_path):
    cfg = _moe_cfg()
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    gen = WorkloadGenerator("chat", seed=3, vocab_size=cfg.vocab_size)
    reqs = [
        dataclasses.replace(r, prompt_len=min(r.prompt_len, 16),
                            max_new_tokens=min(r.max_new_tokens, 5))
        for r in gen.generate(3)
    ]
    base = _run_serve(params, cfg, reqs, gen)
    metrics.reset()  # count the traced run alone
    trace.enable()
    traced = _run_serve(params, cfg, reqs, gen)

    a = {r.rid: list(r.out) for r in base.batcher.finished}
    b = {r.rid: list(r.out) for r in traced.batcher.finished}
    assert a == b, "tracing changed generated tokens"
    # legacy dict view still works and matches the typed events
    assert traced.decision_log == [e.as_dict() for e in traced.decisions]

    evs = trace.default().events()
    names = {e["name"] for e in evs}
    assert "serve.tick" in names
    assert {"serve.admit", "serve.decode", "serve.emit", "serve.observe"} <= names
    assert "controlplane.plan" in names
    assert "traffic.report" in names
    assert any(n.startswith("serve.") and e["cat"] == "decision"
               for e in evs for n in [e["name"]])
    path = str(tmp_path / "serve.json")
    trace.export(path)
    assert trace.validate_file(path) == []
    # metrics saw the same run: one counted tick per serve.tick span (the
    # engine clock can jump idle gaps without stepping)
    reg = metrics.default()
    n_tick_spans = sum(1 for e in evs if e["name"] == "serve.tick")
    assert reg.value("serve.ticks") == n_tick_spans > 0
    assert reg.value("serve.tokens_served") > 0
    # the observatory streamed the gate loads
    assert traced.observatory is not None and traced.observatory.ticks > 0
    assert 0.0 <= traced.observatory.locality_score() <= 1.0


def test_train_bit_identical_with_tracing(tmp_path):
    from repro.data.pipeline import SyntheticLM

    cfg = ModelConfig(
        "tiny-moe", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff=32, capacity_factor=2.0,
                      backend="mixnet"),
    )
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    tcfg = TrainerConfig(total_steps=4, reconfig_every=2,
                         reconfig_min_gain=0.0)

    def losses():
        tr = Trainer(cfg, opt, tcfg, PLAN, seed=0)
        log = tr.train(iter(SyntheticLM(cfg.vocab_size, 16, 4, seed=0)))
        return [float(m["loss"]) for m in log]

    base = losses()
    trace.enable()
    traced = losses()
    assert base == traced, "tracing changed training"
    evs = trace.default().events()
    names = {e["name"] for e in evs}
    assert "train.step" in names
    assert "train.reconfig" in names
    assert validate_events(evs) == []
    reg = metrics.default()
    # the registry is always on: both the base and the traced run count
    assert reg.value("train.steps") == 2 * tcfg.total_steps
    assert reg.value("train.tokens") > 0


def test_trainer_autotune_cache_miss_warns_and_counts(tmp_path, capsys):
    cfg = ModelConfig(
        "tiny-moe", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff=32, capacity_factor=2.0,
                      backend="mixnet"),
    )
    tcfg = TrainerConfig(
        total_steps=1,
        autotune_cache=str(tmp_path / "missing_cache.json"),
        autotune_key="no-such-key",
    )
    Trainer(cfg, AdamWConfig(), tcfg, PLAN, seed=0)
    assert metrics.default().value("autotune.cache_miss") == 1.0
    out = capsys.readouterr().out
    assert "autotune cache miss" in out and "no-such-key" in out


def _inside(span, outer):
    return (span["tid"] == outer["tid"] and outer["ts"] <= span["ts"]
            and span["ts"] + span["dur"] <= outer["ts"] + outer["dur"])


def test_paged_serve_spans_one_admit_per_request_inside_ticks():
    cfg = _moe_cfg()
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    gen = WorkloadGenerator("chat", seed=5, vocab_size=cfg.vocab_size)
    reqs = [
        dataclasses.replace(r, prompt_len=min(r.prompt_len, 14),
                            max_new_tokens=min(r.max_new_tokens, 4))
        for r in gen.generate(4)
    ]
    scfg = ServeConfig(slots=2, max_len=40, paged=True, page_size=8,
                       prefix_cache=False)

    def run():
        eng = ServeEngine(jax.tree.map(lambda a: a, params), cfg, PLAN, scfg)
        eng.run(reqs, gen)
        return eng

    base = run()
    trace.enable()
    traced = run()
    a = {r.rid: list(r.out) for r in base.batcher.finished}
    b = {r.rid: list(r.out) for r in traced.batcher.finished}
    assert a == b and len(b) == len(reqs), "tracing changed generated tokens"

    evs = [e for e in trace.default().events() if e["ph"] == "X"]
    admits = [e for e in evs if e["name"] == "serve.admit"]
    prompts = {r.rid: len(r.prompt) for r in traced.batcher.finished}
    assert sorted(e["args"]["rid"] for e in admits) == sorted(prompts)
    assert all(e["args"]["tokens"] == prompts[e["args"]["rid"]] for e in admits)
    ticks = [e for e in evs if e["name"] == "serve.tick"]
    phases = [e for e in evs if e["name"] in
              ("serve.admit", "serve.decode", "serve.emit", "serve.observe")]
    assert {e["name"] for e in phases} == {"serve.admit", "serve.decode",
                                           "serve.emit", "serve.observe"}
    assert all(any(_inside(p, t) for t in ticks) for p in phases)
    assert validate_events(evs) == []
