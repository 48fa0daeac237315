"""Layer 1: AST lint rules over ``src/``.

Each rule encodes one standing invariant that used to live only in ROADMAP
prose / reviewer memory (see docs/analysis.md for the catalog, suppression
syntax, and how to add a rule):

- ``shard-map-import`` — ``shard_map`` must be imported through
  ``core/compat.py`` (the version shim), never straight from jax.
- ``host-sync`` — hot-path code (trainer step loops, the serving engine,
  ``kernels/``) must not fan one device pytree out into per-element host
  syncs (``float(m["lr"])``, ``float(m["loss"])``, ... each block the
  dispatch queue separately) and must never call ``.item()``. Fetch once
  with ``jax.device_get`` and read the host copy.
- ``obs-contract`` — any function taking ``obs=`` defaults it to ``None``
  (the zero-cost-when-absent contract), span names are
  ``<subsystem>.<signal>`` and metric names ``<subsystem>/<signal>``
  (docs/observability.md grammar); every ``jax.named_scope`` takes a
  literal ``[a-z][a-z0-9_]*`` name (an f-string such as ``f"stage{i}"``
  counts when its literal parts keep to it) and every ``pallas_call``
  passes a literal ``name="<kernel>_<fwd|bwd>[_<part>]"``, the names a
  device trace is read by.
- ``prng-reuse`` — a PRNG key fed to two ``jax.random.*`` consumers
  without an intervening ``split``/``fold_in`` silently correlates the
  two draws.

Rules are pure AST passes: no imports of the linted code, so a broken
module still lints. Findings are suppressed per line with
``# repro: ignore[rule-id]`` (:mod:`repro.analysis.findings`).
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set

from repro.analysis.findings import Finding, filter_suppressed

REPO_ROOT = Path(__file__).resolve().parents[3]
SRC_ROOT = REPO_ROOT / "src"


@dataclass(frozen=True)
class LintConfig:
    """Paths are matched as substrings of the repo-relative posix path."""
    # modules whose loops interleave with device dispatch (rule host-sync)
    hot_paths: Sequence[str] = ("train/trainer.py", "serving/engine.py",
                                "kernels/")
    # the one module allowed to touch jax's shard_map directly
    compat_paths: Sequence[str] = ("core/compat.py",)


DEFAULT_CONFIG = LintConfig()


def _matches(relpath: str, patterns: Sequence[str]) -> bool:
    return any(p in relpath for p in patterns)


# ---------------------------------------------------------------------------
# rule: shard-map-import
# ---------------------------------------------------------------------------


def rule_shard_map_import(tree: ast.AST, relpath: str,
                          cfg: LintConfig) -> List[Finding]:
    if _matches(relpath, cfg.compat_paths):
        return []
    out = []
    msg = ("raw shard_map import — route through core/compat.py "
           "(version shim for the namespace/kwarg moves)")
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            names = {a.name for a in node.names}
            if mod.startswith("jax") and ("shard_map" in mod
                                          or "shard_map" in names):
                out.append(Finding(relpath, node.lineno,
                                   "shard-map-import", msg))
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax") and "shard_map" in a.name:
                    out.append(Finding(relpath, node.lineno,
                                       "shard-map-import", msg))
    return out


# ---------------------------------------------------------------------------
# rule: host-sync
# ---------------------------------------------------------------------------

_HOST_FETCHERS = {"device_get"}          # jax.device_get(...)


def _scope_walk(fn: ast.AST) -> Iterable[ast.AST]:
    """Nodes of a function's own scope — nested def/class bodies excluded
    (they are linted as their own scopes); lambdas stay in the enclosing
    scope."""
    stack = list(fn.body)  # type: ignore[attr-defined]
    while stack:
        n = stack.pop()
        yield n
        for c in ast.iter_child_nodes(n):
            if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            stack.append(c)


def _subscript_base(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Subscript) and isinstance(node.value, ast.Name):
        return node.value.id
    return None


def _assigned_names(target: ast.AST) -> Iterable[str]:
    if isinstance(target, ast.Name):
        yield target.id
    elif isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            yield from _assigned_names(elt)


def rule_host_sync(tree: ast.AST, relpath: str,
                   cfg: LintConfig) -> List[Finding]:
    if not _matches(relpath, cfg.hot_paths):
        return []
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        host_names: Set[str] = set()     # fetched once via jax.device_get
        conversions: Dict[str, List[ast.AST]] = {}
        for node in _scope_walk(fn):
            if isinstance(node, ast.Assign):
                v = node.value
                if (isinstance(v, ast.Call)
                        and isinstance(v.func, ast.Attribute)
                        and v.func.attr in _HOST_FETCHERS):
                    for t in node.targets:
                        host_names.update(_assigned_names(t))
                continue
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            # any .item() is a per-element device sync — never on a hot path
            if isinstance(f, ast.Attribute) and f.attr == "item" \
                    and not node.args:
                out.append(Finding(
                    relpath, node.lineno, "host-sync",
                    ".item() forces a device sync on a hot path — batch "
                    "the fetch with jax.device_get"))
                continue
            # float(m["x"]) / int(m["x"]) / np.asarray(m["x"]) — group by m
            base = None
            if isinstance(f, ast.Name) and f.id in ("float", "int") \
                    and len(node.args) == 1:
                base = _subscript_base(node.args[0])
            elif (isinstance(f, ast.Attribute)
                  and f.attr in ("asarray", "array")
                  and isinstance(f.value, ast.Name)
                  and f.value.id in ("np", "numpy") and node.args):
                base = _subscript_base(node.args[0])
            if base is not None:
                conversions.setdefault(base, []).append(node)
        for name, sites in conversions.items():
            if len(sites) < 2 or name in host_names:
                continue
            for site in sorted(sites, key=lambda n: (n.lineno,
                                                     n.col_offset))[1:]:
                out.append(Finding(
                    relpath, site.lineno, "host-sync",
                    f"{len(sites)} separate host syncs on '{name}' in one "
                    f"scope — fetch the pytree once with jax.device_get "
                    f"and read floats from the host copy"))
    return out


# ---------------------------------------------------------------------------
# rule: obs-contract
# ---------------------------------------------------------------------------

SPAN_NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")
METRIC_NAME_RE = re.compile(r"^[a-z0-9_]+(/[a-z0-9_]+)+$")
SCOPE_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
KERNEL_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*_(fwd|bwd)(_[a-z0-9]+)*$")
_METRIC_METHODS = {"observe", "set", "inc"}


def _callee(node: ast.Call) -> Optional[str]:
    f = node.func
    return f.attr if isinstance(f, ast.Attribute) else \
        f.id if isinstance(f, ast.Name) else None


def _literal_scope(arg: ast.AST) -> bool:
    """A string literal of the scope grammar, or an f-string whose literal
    parts keep to it and start it (``f"block{i}"``)."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return bool(SCOPE_NAME_RE.match(arg.value))
    if not isinstance(arg, ast.JoinedStr) or not arg.values:
        return False
    parts = [v.value if isinstance(v, ast.Constant) else "0"
             for v in arg.values]
    return isinstance(arg.values[0], ast.Constant) and bool(
        SCOPE_NAME_RE.match("".join(parts)))


def _name_findings(node: ast.Call, relpath: str) -> List[Finding]:
    callee = _callee(node)
    if callee == "named_scope" and not (
            node.args and _literal_scope(node.args[0])):
        return [Finding(relpath, node.lineno, "obs-contract",
                        "named_scope takes a literal [a-z][a-z0-9_]* name "
                        "(docs/observability.md: scopes and kernel names)")]
    if callee == "pallas_call":
        name = next((kw.value for kw in node.keywords if kw.arg == "name"),
                    None)
        if not (isinstance(name, ast.Constant)
                and isinstance(name.value, str)
                and KERNEL_NAME_RE.match(name.value)):
            return [Finding(relpath, node.lineno, "obs-contract",
                            "pallas_call without a literal "
                            "name=\"<kernel>_<fwd|bwd>[_<part>]\"")]
    return []


def _arg_default(fn: ast.AST, name: str):
    """(found, default_node_or_None_if_missing) for a parameter by name."""
    a = fn.args  # type: ignore[attr-defined]
    pos = list(a.posonlyargs) + list(a.args)
    n_def = len(a.defaults)
    for i, arg in enumerate(pos):
        if arg.arg == name:
            j = i - (len(pos) - n_def)
            return True, (a.defaults[j] if j >= 0 else None)
    for i, arg in enumerate(a.kwonlyargs):
        if arg.arg == name:
            return True, a.kw_defaults[i]
    return False, None


def rule_obs_contract(tree: ast.AST, relpath: str,
                      cfg: LintConfig) -> List[Finding]:
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            found, default = _arg_default(node, "obs")
            if found and not (isinstance(default, ast.Constant)
                              and default.value is None):
                out.append(Finding(
                    relpath, node.lineno, "obs-contract",
                    f"'{node.name}' takes obs= but does not default it to "
                    f"None — call sites must stay zero-cost un-observed"))
            continue
        if isinstance(node, ast.Call):
            out.extend(_name_findings(node, relpath))
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute) and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        name = node.args[0].value
        if node.func.attr == "span" and not SPAN_NAME_RE.match(name):
            out.append(Finding(
                relpath, node.lineno, "obs-contract",
                f"span name '{name}' violates the <subsystem>.<signal> "
                f"grammar (docs/observability.md)"))
        elif node.func.attr in _METRIC_METHODS \
                and not METRIC_NAME_RE.match(name):
            out.append(Finding(
                relpath, node.lineno, "obs-contract",
                f"metric name '{name}' violates the <subsystem>/<signal> "
                f"grammar (docs/observability.md)"))
    return out


# ---------------------------------------------------------------------------
# rule: prng-reuse
# ---------------------------------------------------------------------------

# jax.random functions that derive keys rather than consuming them
_KEY_DERIVERS = {"split", "fold_in", "PRNGKey", "key", "key_data",
                 "wrap_key_data", "clone"}
_RANDOM_ALIASES = {"jrandom", "jr"}      # `from jax import random as jrandom`


def _consumed_key_name(call: ast.Call) -> Optional[str]:
    """Bare-Name key passed to a consuming jax.random.* call, else None."""
    f = call.func
    if not isinstance(f, ast.Attribute) or f.attr in _KEY_DERIVERS:
        return None
    base = f.value
    is_jax_random = (
        (isinstance(base, ast.Attribute) and base.attr == "random"
         and isinstance(base.value, ast.Name) and base.value.id == "jax")
        or (isinstance(base, ast.Name) and base.id in _RANDOM_ALIASES))
    if not is_jax_random:
        return None
    if call.args and isinstance(call.args[0], ast.Name):
        return call.args[0].id
    return None


def rule_prng_reuse(tree: ast.AST, relpath: str,
                    cfg: LintConfig) -> List[Finding]:
    out = []
    seen: Set = set()            # dedup loop second-pass findings

    def visit_stmt(st: ast.stmt, state: Dict[str, int]) -> None:
        for call in sorted(
                (n for n in ast.walk(st) if isinstance(n, ast.Call)),
                key=lambda n: (n.lineno, n.col_offset)):
            name = _consumed_key_name(call)
            if name is None:
                continue
            if state.get(name, 0) >= 1:
                key = (relpath, call.lineno, name)
                if key not in seen:
                    seen.add(key)
                    out.append(Finding(
                        relpath, call.lineno, "prng-reuse",
                        f"key '{name}' already consumed by a jax.random "
                        f"call on this path — split or fold_in first"))
            state[name] = state.get(name, 0) + 1
        if isinstance(st, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = st.targets if isinstance(st, ast.Assign) \
                else [st.target]
            for t in targets:
                for name in _assigned_names(t):
                    state[name] = 0      # rebound — fresh key

    def scan(stmts: Sequence[ast.stmt], state: Dict[str, int]) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue                 # separate scope
            if isinstance(st, ast.If):
                s_then, s_else = dict(state), dict(state)
                scan(st.body, s_then)
                scan(st.orelse, s_else)
                for k in set(s_then) | set(s_else):
                    state[k] = max(s_then.get(k, 0), s_else.get(k, 0))
            elif isinstance(st, (ast.For, ast.While)):
                # two passes over the body: a key consumed once per
                # iteration without a rebind is cross-iteration reuse;
                # the loop TARGET rebinds every iteration (`for g, r in
                # zip(grads, rngs)` — each r is fresh)
                loop_targets = list(_assigned_names(st.target)) \
                    if isinstance(st, ast.For) else []
                for _ in range(2):
                    for name in loop_targets:
                        state[name] = 0
                    scan(st.body, state)
                scan(st.orelse, state)
            elif isinstance(st, ast.With):
                for item in st.items:
                    visit_stmt(ast.Expr(item.context_expr), state)
                scan(st.body, state)
            elif isinstance(st, ast.Try):
                scan(st.body, state)
                for h in st.handlers:
                    scan(h.body, dict(state))
                scan(st.orelse, state)
                scan(st.finalbody, state)
            else:
                visit_stmt(st, state)

    for fn in ast.walk(tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan(fn.body, {})
    return out


# ---------------------------------------------------------------------------
# rule: axis-name-literal
# ---------------------------------------------------------------------------

# collective ops whose axis argument is the SECOND positional (value first)
_COLLECTIVES_ARG1 = {"psum", "pmean", "pmax", "pmin", "all_gather",
                     "psum_scatter", "all_to_all", "ppermute"}
# ops whose axis argument is the FIRST positional
_COLLECTIVES_ARG0 = {"axis_index"}


def _has_str_literal(node: ast.AST) -> bool:
    """A string constant, or a tuple/list literal containing one."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return True
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_has_str_literal(e) for e in node.elts)
    return False


def rule_axis_name_literal(tree: ast.AST, relpath: str,
                           cfg: LintConfig) -> List[Finding]:
    """Collective axis names must come from the ``launch.mesh`` constants
    (``POD_AXIS`` / ``DATA_AXIS`` / ``MODEL_AXIS``), not inline strings —
    a mesh-layout rename must be one edit, not a repo-wide grep. Applies to
    the axis argument of jax collectives (psum/pmean/all_gather/...), by
    position or as ``axis_name=``."""
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        attr = node.func.attr
        if attr in _COLLECTIVES_ARG1:
            pos = 1
        elif attr in _COLLECTIVES_ARG0:
            pos = 0
        else:
            continue
        axis_args = [kw.value for kw in node.keywords
                     if kw.arg == "axis_name"]
        if len(node.args) > pos:
            axis_args.append(node.args[pos])
        for a in axis_args:
            if _has_str_literal(a):
                out.append(Finding(
                    relpath, node.lineno, "axis-name-literal",
                    f"string-literal axis name in {attr}() — use the "
                    f"repro.launch.mesh axis constants (POD_AXIS / "
                    f"DATA_AXIS / MODEL_AXIS)"))
    return out


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

RuleFn = Callable[[ast.AST, str, LintConfig], List[Finding]]

RULES: Dict[str, RuleFn] = {
    "shard-map-import": rule_shard_map_import,
    "host-sync": rule_host_sync,
    "obs-contract": rule_obs_contract,
    "prng-reuse": rule_prng_reuse,
    "axis-name-literal": rule_axis_name_literal,
}

CATALOG: Dict[str, str] = {
    "shard-map-import": "shard_map imported outside core/compat.py",
    "host-sync": "per-metric device syncs / .item() on a hot path",
    "obs-contract": "obs= without None default, or span/metric/scope/"
                    "kernel name off the naming grammar",
    "prng-reuse": "PRNG key consumed twice without split/fold_in",
    "axis-name-literal": "collective axis name spelled as a string literal "
                         "instead of a launch.mesh constant",
}


def lint_source(source: str, relpath: str,
                cfg: LintConfig = DEFAULT_CONFIG,
                rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Lint one file's source; suppressions already applied."""
    tree = ast.parse(source, filename=relpath)
    findings: List[Finding] = []
    for rule_id in (rules or RULES):
        findings.extend(RULES[rule_id](tree, relpath, cfg))
    return filter_suppressed(findings, {relpath: source})


def lint_paths(paths: Iterable[Path], *, root: Path = REPO_ROOT,
               cfg: LintConfig = DEFAULT_CONFIG,
               rules: Optional[Sequence[str]] = None) -> List[Finding]:
    findings: List[Finding] = []
    for path in sorted(paths):
        rel = path.resolve().relative_to(root).as_posix() \
            if path.resolve().is_relative_to(root) else path.as_posix()
        findings.extend(lint_source(path.read_text(), rel, cfg,
                                    rules=rules))
    return findings


def run_repo_lint(cfg: LintConfig = DEFAULT_CONFIG) -> List[Finding]:
    """The repo gate: every lint rule over every module under ``src/``."""
    return lint_paths(SRC_ROOT.rglob("*.py"), root=REPO_ROOT, cfg=cfg)
