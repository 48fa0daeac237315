"""Device time by sublayer (``bench/scopes.py``): attribution on a hand-made
HLO text and trace, the scopes the program's tiny steps compile to on the
CPU, and the GBN kernels' names in interpret mode."""
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from bench import harness, scopes as S, trace as T
from bench.tests import tiny

# the layout of a TPU module's text: fused computations first, a while
# body, an ENTRY whose header holds an /*index=*/ comment
HLO = """HloModule jit_train_step, entry_computation_layout={(f32[8,16]{1,0})->f32[8,16]{1,0}}

%fused_computation (param_0.1: f32[8,16]) -> f32[8,16] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %convolution.1 = f32[8,16]{1,0} convolution(%param_0.1, %param_0.1), window={size=1}, dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/transpose(jvp(stage0))/block1/conv/conv_general_dilated" stack_frame_id=3}
  ROOT %add.1 = f32[8,16]{1,0} add(%convolution.1, %param_0.1), metadata={op_name="jit(train_step)/transpose(jvp(stage0))/block1/act/add"}
}

%body.2 (p.1: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p.1 = (s32[], f32[8,16]{1,0}) parameter(0)
  %gte.1 = f32[8,16]{1,0} get-tuple-element(%p.1), index=1
  %gbn_fwd_stats.3 = f32[8,16]{1,0:T(8,128)} custom-call(%gte.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(stage0)/block0/gbn/gbn/gbn_fwd_stats/pallas_call" stack_frame_id=6}
  %copy.4 = f32[8,16]{0,1:T(8,128)} copy(%gbn_fwd_stats.3)
  ROOT %tuple.1 = (s32[], f32[8,16]{1,0}) tuple(%gte.1, %copy.4)
}

ENTRY %main.9 (x.1: f32[8,16], /*index=1*/y.1: f32[8,16]) -> f32[8,16] {
  %x.1 = f32[8,16]{1,0} parameter(0), metadata={op_name="x"}
  %fusion.2 = f32[8,16]{1,0} fusion(%x.1), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(train_step)/transpose(jvp(stage0))/block1/act/add"}
  %while.5 = (s32[], f32[8,16]{1,0}) while(%fusion.2), condition=%cond.1, body=%body.2, metadata={op_name="jit(train_step)/jvp()/while"}
  %add.6 = f32[8,16]{1,0} add(%x.1, %x.1), metadata={op_name="jit(train_step)/transpose(jvp())/add_any"}
  ROOT %mul.7 = f32[8,16]{1,0} multiply(%add.6, %add.6), metadata={op_name="jit(train_step)/optimizer/mul;jit(train_step)/mul"}
}
"""


def _trace():
    ops = [T.Op("fusion.2", 100, 50, 0),
           T.Op("while.5", 200, 200, 0),         # encloses its body's ops
           T.Op("gbn_fwd_stats.3", 210, 90, 0),
           T.Op("copy.4", 300, 30, 0),
           T.Op("add.6", 500, 20, 0),
           T.Op("mul.7", 520, 5, 0),
           T.Op("fusion.99", 600, 10, 0),        # not in the program
           T.Op("fusion.2", 2000, 50, 0)]        # after the window
    return T.Trace(ops, [T.Span(T.WINDOW_SPAN, 0, 1000)], 1)


def _ctx(text=HLO, steps=2):
    outcome = types.SimpleNamespace(facts={"hlo": {"step": text},
                                           "steps": steps})
    return types.SimpleNamespace(outcome=outcome, trace=_trace(),
                                 window=(0, 1000))


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/transpose(jvp(stage1))/block3/conv/"
     "conv_general_dilated", ["stage1", "block3", "conv"]),
    ("jit(train_step)/jvp()/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/flash_attention_rope/"
     "flash_attention_rope_fwd/while/body/cond/branch_1_fun/dot_general",
     ["attention", "flash_attention_rope", "flash_attention_rope_fwd"]),
    ("jit(train_step)/transpose(jvp(ce))/while/body/closed_call/checkpoint/"
     "dot_general", ["ce"]),
    ("jit(train_step)/jvp(jit(take_along_axis))/gather", []),
    ("jit(train_step)/jvp()/reshape;jit(train_step)/jvp(gbn)/reshape",
     ["gbn"]),
    ("params['stages'][0]", []),
])
def test_scopes_strip_jax_wrappers_and_the_primitive(op_name, want):
    assert S.scopes(op_name) == want


def test_the_innermost_sublayer_wins():
    assert S.sublayer(["stage0", "block1", "gbn", "gbn", "gbn_bwd_dx"]) \
        == "gbn"
    assert S.sublayer(["attention", "flash_attention"]) == "attention"
    assert S.sublayer(["stage0", "block1"]) == S.UNSCOPED


def test_op_scopes_of_fusions_while_bodies_and_bare_copies():
    paths = S.op_scopes(HLO)
    # a fusion goes by its heaviest op, not by its root
    assert S.sublayer(paths["fusion.2"]) == "conv"
    assert paths["gbn_fwd_stats.3"] == ["stage0", "block0", "gbn", "gbn",
                                        "gbn_fwd_stats"]
    # a copy the compiler made without metadata takes its operand's path
    assert S.sublayer(paths["copy.4"]) == "gbn"
    assert S.sublayer(paths["while.5"]) == S.UNSCOPED
    assert S.sublayer(paths["mul.7"]) == "optimizer"
    assert paths["x.1"] == []


def test_seconds_by_sublayer_counts_own_time_once():
    tr = _trace()
    secs = S.seconds_by_sublayer(tr, (0, 1000), [HLO])
    # the while's own time is 200 - 90 - 30; add_any and the op of another
    # program are unscoped; the op after the window is left out
    assert secs == pytest.approx({"conv": 50e-9, "gbn": 120e-9,
                                  "optimizer": 5e-9,
                                  S.UNSCOPED: (80 + 20 + 10) * 1e-9})
    assert sum(secs.values()) == pytest.approx(T.busy(tr, (0, 1000)))


def test_ms_per_step_and_a_program_without_the_names():
    ctx = _ctx(steps=2)
    assert S.ms_per_step(ctx, "gbn") == pytest.approx(120e-9 * 1e3 / 2)
    # a scope the program carries but the window never ran reads 0
    assert S.ms_per_step(ctx, "optimizer") == pytest.approx(5e-9 * 1e3 / 2)
    assert S.ms_per_step(ctx, "attention") is None
    bare = "\n".join(line.split(", metadata=")[0] for line in
                     HLO.splitlines())
    assert S.ms_per_step(_ctx(bare), "gbn") is None


NEW = {"gbn_ms.gbn": "gbn", "conv_ms.gbn": "conv",
       "attention_ms.lm_train": "attention", "mlp_ms.lm_train": "mlp",
       "ce_ms.lm_train": "ce"}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_each_reader_reads_its_scope(metric):
    read = harness.reader_of(metric)
    assert read(_ctx()) == S.ms_per_step(_ctx(), NEW[metric])
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == ("ms", "lower", "device_trace", "sublayers")


def test_scopes_names_no_cell():
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    words = {w["name"] for w in bench["workloads"]} | {
        w["traffic"] for w in bench["workloads"]} | {
        c["name"] for c in bench["configs"]}
    text = open(os.path.join(harness.ROOT, "bench", "scopes.py")).read()
    assert not [w for w in words if w in text]


# the sublayers each training step holds, on the jnp path
CELLS = {"resnet44.lb4096-gbn": {"conv", "gbn", "act", "head", "ce",
                                 "optimizer"},
         "qwen3-1.7b.train-8x2048": {"embed", "norm", "attention", "mlp",
                                     "ce", "optimizer"}}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_every_heavy_op_of_a_tiny_step_has_one_sublayer(name):
    spec = tiny.cell(name)
    prog = harness.driver_of(spec).program(
        spec.config, spec.traffic, harness.reference_of(spec.config))
    text = prog.compiled.as_text()
    paths = S.op_scopes(text)
    heavy = [i.name for c in S._parse(text).values() for i in c
             if i.opcode in ("dot", "convolution", "custom-call")]
    assert heavy
    for n in heavy:
        assert len({c for c in paths[n] if c in S.SUBLAYERS}) == 1, \
            (n, paths[n])
    assert {S.sublayer(p) for p in paths.values()} - {S.UNSCOPED} \
        == CELLS[name]


def test_gbn_kernels_carry_the_scope_and_their_names():
    """One GBN layer's gradient through the Pallas kernels (interpret mode
    on the CPU): its forward and backward instructions carry ``gbn`` and
    the kernel's names, the backward's under JAX's transpose."""
    from repro.core import gbn as G

    params, state = G.gbn_init(8)

    def loss(p, x):
        y, _ = G.gbn_apply(p, state, x, ghost_batch_size=4,
                           use_kernels=True)
        return jnp.sum(y * y)

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 4, 8))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    paths = S.op_scopes(text)
    kernels = {"gbn_fwd_stats", "gbn_fwd_normalize", "gbn_bwd_stats",
               "gbn_bwd_dx"}
    found = {}
    for n, p in paths.items():
        for k in kernels & set(p):
            assert S.sublayer(p) == "gbn", (n, p)
            found.setdefault(k, []).append(n)
    assert set(found) == kernels
    op_names = {i.name: i.op_name for c in S._parse(text).values()
                for i in c}
    for k in ("gbn_bwd_stats", "gbn_bwd_dx"):
        assert all("transpose(" in op_names[n] for n in found[k]
                   if op_names[n])
