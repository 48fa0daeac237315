"""The reduction from a profiler trace to busy time, idle gaps and time per
operation, on a hand-made trace and on one recorded on the CPU."""
import pytest

from bench import trace as T


def _trace():
    ops = [T.Op("conv", 100, 50, 0), T.Op("conv", 150, 30, 0),    # abut
           T.Op("gbn", 300, 100, 0), T.Op("gbn", 950, 100, 0),     # past end
           T.Op("conv", 100, 200, 1)]                               # chip 1
    spans = [T.Span(T.WINDOW_SPAN, 0, 1000), T.Span("bench.step", 0, 90),
             T.Span("serve.admit", 190, 100), T.Span("bench.step", 180, 200)]
    return T.Trace(ops, spans, 2)


def test_union_merges_and_clips():
    assert T.union([(5, 10), (0, 3), (8, 12), (2, 4)], 1, 11) == [
        (1, 4), (5, 11)]


def test_busy_is_the_union_averaged_over_chips():
    tr = _trace()
    # chip 0: [100, 180) + [300, 400) + [950, 1000) = 230; chip 1: 200
    assert T.busy(tr, (0, 1000)) == pytest.approx((230 + 200) / 2 / 1e9)


def test_idle_gaps_and_their_host_labels():
    tr = _trace()
    gaps = T.idle_gaps(tr, (0, 1000))
    assert gaps == [(0, 100), (180, 300), (400, 950)]
    assert T.host_label(tr.spans, 0, 100) == "bench.step"
    # innermost span open at the gap's middle (240): serve.admit
    assert T.host_label(tr.spans, 180, 300) == "serve.admit"
    assert T.host_label(tr.spans, 400, 950) == "no host span"


def test_op_seconds_sum_by_name_and_breakdown_order():
    tr = _trace()
    per = T.op_seconds(tr, (0, 1000))
    assert per == pytest.approx({"conv": 280e-9, "gbn": 200e-9})
    bd = T.breakdown(tr, (0, 1000), top=2)
    assert [n for n, _ in bd["device_ops"]] == ["conv", "gbn"]
    assert bd["idle_gaps"][0] == ["no host span", pytest.approx(550e-9)]


def test_op_time_leaves_out_the_ops_nested_inside():
    ops = [T.Op("while.1", 0, 100, 0), T.Op("fusion.2", 10, 30, 0),
           T.Op("custom.3", 50, 20, 0), T.Op("fusion.2", 200, 10, 0)]
    per = T.op_seconds(T.Trace(ops, [], 1), (0, 1000))
    assert per == pytest.approx({"while.1": 50e-9, "fusion.2": 40e-9,
                                 "custom.3": 20e-9})


def test_op_names_are_hlo_instruction_names():
    assert T.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), "
                     "kind=kLoop") == "fusion.12"
    assert T.op_name("copy-start") == "copy-start"


def test_window_of_requires_one_window_span():
    tr = _trace()
    assert T.window_of(tr) == (0, 1000)
    with pytest.raises(ValueError):
        T.window_of(T.Trace([], [], 0))


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=T.profile_options())
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    lo, hi = T.window_of(tr)
    step = [s for s in tr.spans if s.name == "bench.step"]
    assert len(step) == 1 and lo <= step[0].start and step[0].end <= hi
    assert tr.chips == 0 and tr.ops == []     # no TPU plane on the CPU
