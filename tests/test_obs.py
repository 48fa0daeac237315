"""Observability layer: span tracer (nesting, Chrome export, zero-cost
disabled path), streaming histograms vs numpy quantiles, the metrics
registry (JSONL export, summary table, kind safety), the MetricsLogger
dedup shims, and the engine/trainer SLO wiring."""
import dataclasses
import json
import tracemalloc

import numpy as np
import pytest

from repro.obs import Observability
from repro.obs.metrics import Histogram, MetricsLogger, Registry
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Tracer


# ---------------------------------------------------------------------------
# tracer (tier0 — pure python, runs in --quick)
# ---------------------------------------------------------------------------


@pytest.mark.tier0
def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("outer", k=1):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b"):
            pass
    # "X" events append on exit: children close before the parent
    names = [e["name"] for e in tr.events]
    assert names == ["inner_a", "inner_b", "outer"]
    by = {e["name"]: e for e in tr.events}
    out, a, b = by["outer"], by["inner_a"], by["inner_b"]
    # containment on one pid/tid track is what Perfetto nests by
    assert out["ts"] <= a["ts"] and out["ts"] <= b["ts"]
    assert a["ts"] + a["dur"] <= out["ts"] + out["dur"] + 1e-6
    assert b["ts"] >= a["ts"] + a["dur"] - 1e-6       # siblings ordered
    assert out["args"] == {"k": 1}
    assert out["tid"] == a["tid"] == b["tid"]


@pytest.mark.tier0
def test_chrome_trace_json_valid(tmp_path):
    tr = Tracer()
    with tr.span("root"):
        with tr.span("child", i=3):
            pass
    path = tmp_path / "trace.json"
    tr.write_chrome(str(path))
    events = json.loads(path.read_text())
    assert isinstance(events, list) and len(events) == 2
    for ev in events:
        assert {"name", "ph", "pid", "tid", "dur", "ts"} <= set(ev)
        assert ev["ph"] == "X" and ev["dur"] >= 0.0


@pytest.mark.tier0
def test_disabled_tracer_zero_cost():
    tr = Tracer(enabled=False)
    # the disabled path returns ONE shared singleton: no per-span object
    s1 = tr.span("a", k=1)
    s2 = tr.span("b")
    assert s1 is s2 is NULL_SPAN is NULL_TRACER.span("c")
    tracemalloc.start()
    for i in range(100):
        with tr.span("hot", step=i):
            pass
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 4096                     # no event/span allocations
    assert tr.events == [] and NULL_TRACER.events == []


@pytest.mark.tier0
def test_tracer_clear():
    tr = Tracer()
    with tr.span("a"):
        pass
    tr.clear()
    assert tr.events == []


# ---------------------------------------------------------------------------
# histograms / registry (tier0)
# ---------------------------------------------------------------------------


@pytest.mark.tier0
@pytest.mark.parametrize("dist", ["uniform", "lognormal", "negative"])
def test_histogram_quantiles_vs_numpy(dist):
    rng = np.random.RandomState(0)
    x = {"uniform": rng.uniform(0.5, 20.0, 20_000),
         "lognormal": rng.lognormal(0.0, 1.0, 20_000),
         "negative": -rng.lognormal(0.0, 0.5, 20_000)}[dist]
    h = Histogram()
    for v in x:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        got, want = h.quantile(q), float(np.quantile(x, q))
        assert got == pytest.approx(want, rel=0.03), (q, got, want)
    assert h.count == len(x)
    assert h.quantile(0.0) == pytest.approx(x.min())
    assert h.quantile(1.0) == pytest.approx(x.max())


@pytest.mark.tier0
def test_histogram_exact_fields():
    h = Histogram()
    for v in (1.0, 2.0, 0.0, -3.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["mean"] == pytest.approx(0.0)
    assert s["min"] == -3.0 and s["max"] == 2.0 and s["last"] == -3.0


@pytest.mark.tier0
def test_registry_jsonl_and_summary(tmp_path):
    reg = Registry()
    reg.inc("req", 3)
    reg.set("depth", 7.0)
    for v in (0.1, 0.2, 0.3):
        reg.observe("lat_s", v)
    path = tmp_path / "metrics.jsonl"
    reg.write_jsonl(str(path))
    reg.write_jsonl(str(path))             # append mode: 2 runs accumulate
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rows) == 6
    by = {r["name"]: r for r in rows[:3]}
    assert by["req"]["kind"] == "counter" and by["req"]["value"] == 3
    assert by["depth"]["kind"] == "gauge" and by["depth"]["value"] == 7.0
    lat = by["lat_s"]
    assert lat["kind"] == "histogram" and lat["count"] == 3
    assert {"p50", "p95", "p99", "mean"} <= set(lat)
    assert all("ts" in r for r in rows)
    table = reg.summary_table()
    for name in ("req", "depth", "lat_s"):
        assert name in table


@pytest.mark.tier0
def test_registry_kind_mismatch_raises():
    reg = Registry()
    reg.inc("n")
    with pytest.raises(TypeError):
        reg.observe("n", 1.0)


@pytest.mark.tier0
def test_observability_bundle(tmp_path):
    obs = Observability()
    with obs.span("work"):
        obs.registry.observe("x", 1.0)
    obs.write(str(tmp_path / "t.json"), str(tmp_path / "m.jsonl"))
    assert json.loads((tmp_path / "t.json").read_text())
    assert (tmp_path / "m.jsonl").read_text().strip()
    obs.clear()
    assert obs.tracer.events == [] and obs.registry.names() == []


@pytest.mark.tier0
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_device_trace_failure_raises_only_on_tpu(monkeypatch, tmp_path,
                                                 backend):
    """A profiler that cannot start degrades to a warning off the TPU; on
    the TPU the run stops rather than report an untraced window."""
    import jax
    from repro.obs.trace import device_trace

    def refuse(logdir):
        raise RuntimeError("profiler already active")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if backend == "tpu":
        with pytest.raises(RuntimeError, match="already active"):
            with device_trace(str(tmp_path)):
                pass
    else:
        with pytest.warns(UserWarning, match="device trace unavailable"):
            with device_trace(str(tmp_path)):
                pass


# ---------------------------------------------------------------------------
# MetricsLogger dedup: one implementation, both legacy import paths
# ---------------------------------------------------------------------------


@pytest.mark.tier0
def test_metrics_logger_single_implementation():
    from repro.core.metrics import MetricsLogger as core_ML
    from repro.experiments.metrics import MetricsLogger as exp_ML
    assert core_ML is exp_ML is MetricsLogger
    assert core_ML.__module__ == "repro.obs.metrics"


@pytest.mark.tier0
def test_metrics_logger_attach_registry():
    reg = Registry()
    ml = MetricsLogger()
    ml.attach_registry(reg, prefix="train/")
    ml.log(0, loss=2.0)
    ml.log(1, loss=1.5)
    ml.set_series("distance", [0, 1], [0.1, 0.2])
    assert ml.series("loss") == ([0, 1], [2.0, 1.5])  # logger unchanged
    assert reg.histogram("train/loss").count == 2
    assert reg.histogram("train/distance").last == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# wiring: engine SLOs + trainer telemetry (tier1 — compiles tiny models)
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_engine_slo_metrics_under_poisson_trace():
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.serving import ContinuousEngine, poisson_trace
    import jax
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = poisson_trace(cfg, 5, rate=0.7, seed=0,
                         prompt_len_choices=(4, 8),
                         new_token_choices=(4, 8))
    obs = Observability()
    eng = ContinuousEngine(params, cfg, num_slots=2, max_len=32,
                           layout="paged", page_size=8, total_pages=9,
                           obs=obs)
    comps = eng.run(reqs)
    useful = sum(len(c.tokens) for c in comps.values())
    reg = obs.registry
    # SLO set: per-request latencies observed once per completion
    assert reg.histogram("serve/ttft_s").count == len(comps)
    assert reg.histogram("serve/e2e_s").count == len(comps)
    assert reg.histogram("serve/itl_s").count >= useful - len(comps)
    # per-tick scheduler gauges sampled once per decode step
    for name in ("serve/queue_depth", "serve/slot_occupancy",
                 "serve/page_pool_util"):
        assert reg.histogram(name).count == eng.steps
    assert 0.0 <= reg.histogram("serve/page_pool_util").vmax <= 1.0
    # useful vs raw accounting: raw counts every decoded lane-token
    st = eng.stats()
    assert st["useful_tokens"] == useful
    assert st["raw_tokens"] >= st["useful_tokens"]
    assert st["dropped_tokens"] == st["raw_tokens"] - useful
    assert reg.gauge("serve/useful_tokens").value == useful
    # spans from every hot path made it into the trace
    names = {e["name"] for e in obs.tracer.events}
    assert {"serve.admit", "serve.decode_step", "serve.run"} <= names


@pytest.mark.tier1
def test_trainer_emits_obs(tmp_path):
    from repro.configs.paper_models import F1_MNIST
    from repro.core import LargeBatchConfig, Regime
    from repro.data.synthetic import teacher_classification
    from repro.models.cnn import model_fns
    from repro.train.trainer import train_vision
    cfg = dataclasses.replace(F1_MNIST, input_shape=(8, 8, 1),
                              hidden_sizes=(16,), ghost_batch_size=16)
    data = teacher_classification(0, n_train=128, n_test=64,
                                  input_shape=(8, 8, 1), n_classes=10)
    lb = LargeBatchConfig(batch_size=32, base_batch_size=32,
                          ghost_batch_size=16)
    regime = Regime(base_lr=0.05, total_steps=4, drop_every=4)
    obs = Observability()
    train_vision(model_fns(cfg), cfg, data, lb, regime, obs=obs)
    reg = obs.registry
    assert reg.histogram("train/step_time_s").count == 4
    assert reg.counter("train/steps").value == 4
    assert reg.gauge("train/batch_size").value == 32
    assert reg.gauge("train/lr").value > 0
    assert reg.histogram("train/grad_norm").count == 4
    # logger series mirror into the registry under train/
    assert reg.histogram("train/distance").count >= 1
    spans = [e["name"] for e in obs.tracer.events]
    assert spans.count("train.step") == 4
