"""repro.analysis: fixture pairs per lint rule, suppression semantics,
trace-auditor unit checks, bench-gate units (tier 0 — seconds, no model
code), plus the repo-wide gates (tier 1): lint + kernel contracts clean on
src/, and the trace auditor proving no-callback / no-f64 / donation
aliasing on the hot entry points.
"""
import json
from pathlib import Path

import pytest

from repro.analysis.findings import Finding, render, suppressions
from repro.analysis.lint import (DEFAULT_CONFIG, LintConfig, lint_source,
                                 run_repo_lint)

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "analysis"

# the fixture dir plays the hot path so host-sync fires on its snippets
FIXTURE_CFG = LintConfig(hot_paths=("fixtures/analysis/",))

RULE_STEMS = {
    "shard-map-import": "shard_map",
    "host-sync": "host_sync",
    "obs-contract": "obs_contract",
    "prng-reuse": "prng_reuse",
    "axis-name-literal": "axis_literal",
}


def _lint_fixture(name: str):
    path = FIXTURES / name
    rel = f"fixtures/analysis/{name}"
    return lint_source(path.read_text(), rel, FIXTURE_CFG)


# ---------------------------------------------------------------------------
# tier 0: every rule has a bad/good fixture pair — executable docs
# ---------------------------------------------------------------------------


@pytest.mark.tier0
@pytest.mark.parametrize("rule", sorted(RULE_STEMS))
def test_rule_fixture_pair(rule):
    stem = RULE_STEMS[rule]
    bad = _lint_fixture(f"{stem}_bad.py")
    good = _lint_fixture(f"{stem}_good.py")
    assert any(f.rule == rule for f in bad), \
        f"{stem}_bad.py should trip {rule}:\n{render(bad)}"
    assert all(f.rule != rule for f in good), \
        f"{stem}_good.py should pass {rule}:\n{render(good)}"
    # good fixtures are fully clean, not merely clean for their own rule
    assert not good, render(good)


@pytest.mark.tier0
def test_host_sync_fixture_details():
    bad = _lint_fixture("host_sync_bad.py")
    msgs = [f.message for f in bad if f.rule == "host-sync"]
    # 3 float(m[...]) sites -> findings on the 2nd and 3rd, + one .item()
    assert sum(".item()" in m for m in msgs) == 1
    assert sum("separate host syncs" in m for m in msgs) == 2


@pytest.mark.tier0
def test_suppression_silences_only_the_named_rule():
    src = (FIXTURES / "prng_reuse_bad.py").read_text()
    line = "    b = jax.random.uniform(rng, shape)"
    assert line in src
    ok = src.replace(line, line + "  # repro: ignore[prng-reuse]")
    assert lint_source(ok, "x.py") == []
    wrong = src.replace(line, line + "  # repro: ignore[host-sync]")
    assert any(f.rule == "prng-reuse" for f in lint_source(wrong, "x.py"))


@pytest.mark.tier0
def test_suppressions_parse_multiple_rules():
    sup = suppressions("x = 1  # repro: ignore[host-sync, prng-reuse]\n")
    assert sup == {1: {"host-sync", "prng-reuse"}}


@pytest.mark.tier0
def test_prng_reuse_loop_target_rebinds_each_iteration():
    # `for g, r in zip(...)` rebinds r every iteration — NOT reuse
    # (the core/noise.py ghost-noise pattern)
    src = (
        "import jax\n\n\n"
        "def noise(leaves, rngs):\n"
        "    out = []\n"
        "    for g, r in zip(leaves, rngs):\n"
        "        out.append(jax.random.normal(r, g.shape))\n"
        "    return out\n")
    assert lint_source(src, "x.py") == []
    # ...but a key from OUTSIDE the loop consumed each iteration IS reuse
    src2 = (
        "import jax\n\n\n"
        "def noise(leaves, rng):\n"
        "    out = []\n"
        "    for g in leaves:\n"
        "        out.append(jax.random.normal(rng, g.shape))\n"
        "    return out\n")
    assert any(f.rule == "prng-reuse" for f in lint_source(src2, "x.py"))


@pytest.mark.tier0
def test_obs_contract_branch_grammar():
    src = (
        "def f(reg):\n"
        "    reg.observe('serve/ttft_s', 1.0)\n"
        "    reg.inc('bad metric')\n")
    fs = lint_source(src, "x.py")
    assert [f.line for f in fs if f.rule == "obs-contract"] == [3]


@pytest.mark.tier0
@pytest.mark.parametrize("call,ok", [
    ('jax.named_scope("attention")', True),
    ('jax.named_scope(f"block{i}")', True),
    ('jax.named_scope(name)', False),
    ('jax.named_scope("Attention")', False),
    ('jax.named_scope(f"{i}_block")', False),
    ('pl.pallas_call(k, name="gbn_bwd_dx")', True),
    ('pl.pallas_call(k, name="flash_attention_fwd")', True),
    ('pl.pallas_call(k)', False),
    ('pl.pallas_call(k, name="gbn")', False),
    ('pl.pallas_call(k, name=label)', False),
])
def test_obs_contract_scope_and_kernel_names(call, ok):
    src = f"def f(k, i, name, label):\n    return {call}\n"
    fs = [f for f in lint_source(src, "x.py") if f.rule == "obs-contract"]
    assert (not fs) is ok, fs


# ---------------------------------------------------------------------------
# tier 0: trace auditor units
# ---------------------------------------------------------------------------


@pytest.mark.tier0
def test_audit_jaxpr_flags_callbacks():
    import jax
    import jax.numpy as jnp

    from repro.analysis.trace_audit import audit_jaxpr

    def bad(x):
        jax.debug.print("x = {x}", x=x)
        return x * 2.0

    fs = audit_jaxpr(bad, (jnp.ones((2,)),), name="bad", path="t.py")
    assert any(f.rule == "trace-callback" for f in fs), render(fs)

    def good(x):
        return x * 2.0

    assert audit_jaxpr(good, (jnp.ones((2,)),), name="g", path="t.py") == []


@pytest.mark.tier0
def test_audit_jaxpr_flags_f64():
    import jax
    import jax.numpy as jnp

    from repro.analysis.trace_audit import audit_jaxpr

    def widen(x):
        return x.astype(jnp.float64) * 2.0

    with jax.enable_x64(True):
        fs = audit_jaxpr(widen, (jnp.ones((2,), jnp.float32),),
                         name="widen", path="t.py")
    assert any(f.rule == "trace-f64" for f in fs), render(fs)


@pytest.mark.tier0
def test_audit_jaxpr_recurses_into_scan():
    import jax
    import jax.numpy as jnp

    from repro.analysis.trace_audit import audit_jaxpr

    def scanned(x):
        def body(c, _):
            jax.debug.print("c = {c}", c=c)
            return c + 1.0, None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    fs = audit_jaxpr(scanned, (jnp.float32(0.0),), name="s", path="t.py")
    assert any(f.rule == "trace-callback" for f in fs), render(fs)


@pytest.mark.tier0
def test_audit_donation_positive_and_negative():
    import jax.numpy as jnp

    from repro.analysis.trace_audit import audit_donation

    def f(a, b):
        return a + 1.0, b

    ok = audit_donation(f, (jnp.ones((4,)), jnp.ones((4,))), (0,),
                        name="f", path="t.py")
    assert ok == [], render(ok)

    def g(a, b):
        return b * 2.0          # 'a' has no same-shaped output to reuse

    bad = audit_donation(g, (jnp.ones((3,)), jnp.ones((4,))), (0,),
                         name="g", path="t.py")
    assert any(f_.rule == "trace-donation" for f_ in bad), render(bad)


@pytest.mark.tier0
def test_recompile_census_budget():
    from repro.analysis.trace_audit import Entry, audit_variants

    over = Entry("e", "p.py", build=None,
                 static_knobs={"a": 4, "b": 4}, variant_budget=8)
    assert [f.rule for f in audit_variants(over)] == ["recompile-hazard"]
    under = Entry("e", "p.py", build=None,
                  static_knobs={"a": 2, "b": 2}, variant_budget=8)
    assert audit_variants(under) == []


# ---------------------------------------------------------------------------
# tier 0: kernel contract checker units
# ---------------------------------------------------------------------------


@pytest.mark.tier0
def test_kernel_contracts_flag_missing_oracle(tmp_path):
    from repro.analysis.kernel_contracts import check_oracle_pairing

    kdir = tmp_path / "kernels"
    kdir.mkdir()
    (kdir / "foo.py").write_text("def foo_pallas(x):\n    return x\n")
    (kdir / "ref.py").write_text("def foo_ref(x):\n    return x\n")
    doc = tmp_path / "kernels.md"

    # undocumented kernel
    doc.write_text("# kernels\n")
    fs = check_oracle_pairing(kdir, doc)
    assert any(f.rule == "kernel-doc" for f in fs), render(fs)

    # documented but no oracle on its contract row
    doc.write_text("| op | kernel |\n|---|---|\n"
                   "| `foo` | `foo.foo_pallas` |\n")
    fs = check_oracle_pairing(kdir, doc)
    assert any(f.rule == "kernel-oracle" for f in fs), render(fs)

    # docs cite a deleted oracle
    doc.write_text("| op | kernel | oracle |\n|---|---|---|\n"
                   "| `foo` | `foo.foo_pallas` | `ref.gone_ref` |\n")
    fs = check_oracle_pairing(kdir, doc)
    assert any(f.rule == "kernel-oracle" and "gone_ref" in f.message
               for f in fs), render(fs)

    # paired: clean
    doc.write_text("| op | kernel | oracle |\n|---|---|---|\n"
                   "| `foo` | `foo.foo_pallas` | `ref.foo_ref` |\n")
    assert check_oracle_pairing(kdir, doc) == []


@pytest.mark.tier0
def test_tile_alignment_sweep_clean():
    from repro.analysis.kernel_contracts import check_tile_alignment
    fs = check_tile_alignment()
    assert fs == [], render(fs)


# ---------------------------------------------------------------------------
# tier 0: bench gate units
# ---------------------------------------------------------------------------


def _write_bench(path, name, values):
    rows = [{"ts": f"t{i}", "name": name, "us_per_call": v, "derived": ""}
            for i, v in enumerate(values)]
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")


@pytest.mark.tier0
def test_bench_gate_flags_regression(tmp_path):
    from repro.analysis.bench_gate import check_bench_regressions

    _write_bench(tmp_path / "BENCH_a.json", "a", [100, 104, 98, 250])
    fs = check_bench_regressions(tmp_path)
    assert len(fs) == 1 and fs[0].rule == "bench-regression", render(fs)
    assert "+1" in fs[0].message and "a:" in fs[0].message


@pytest.mark.tier0
def test_bench_gate_tolerates_noise_and_short_history(tmp_path):
    from repro.analysis.bench_gate import check_bench_regressions

    # +30% < the 50% default tolerance
    _write_bench(tmp_path / "BENCH_a.json", "a", [100, 104, 98, 130])
    # regressed but only 1 prior row: not enough history to judge
    _write_bench(tmp_path / "BENCH_b.json", "b", [100, 300])
    assert check_bench_regressions(tmp_path) == []
    # the improvement direction never fires
    _write_bench(tmp_path / "BENCH_c.json", "c", [300, 310, 290, 100])
    assert check_bench_regressions(tmp_path) == []


# ---------------------------------------------------------------------------
# tier 1: the repo-wide gates
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_repo_lint_gate():
    fs = run_repo_lint()
    assert fs == [], "\n" + render(fs)


@pytest.mark.tier1
def test_repo_kernel_contract_gate():
    from repro.analysis.kernel_contracts import run_kernel_contracts
    fs = run_kernel_contracts()
    assert fs == [], "\n" + render(fs)


@pytest.mark.tier1
def test_trace_audit_gate():
    """Traces every registry entry and (for the donating entries:
    train steps, decode step, fused prefill) compiles and proves the
    input_output_alias header covers every donated leaf."""
    from repro.analysis.trace_audit import ENTRIES, run_trace_audit
    names = {e.name for e in ENTRIES}
    assert {"vision_train_step", "lm_train_step", "decode_step",
            "prefill_fused", "flash_decode_paged"} <= names
    fs = run_trace_audit()
    assert fs == [], "\n" + render(fs)
