"""Use-after-donate analysis (LGB009) + donation-liveness runtime assert.

PR 12's cross-iteration buffer donation (`Config.tpu_donate_buffers`,
``jax.jit(..., donate_argnums=...)``) frees the previous iteration's
grad/hess/score HBM for the next — on a TPU that is the difference
between fitting the 1M-row problem and OOMing.  Two silent failure
modes guard-rail it:

  * **use-after-donate** — a donated buffer is INVALID after the call;
    jax raises only when the deleted array is actually touched, which on
    the async dispatch path can be iterations later and rank-dependent.
    The AST pass maps every ``jax.jit(..., donate_argnums=...)`` site
    (assignment, ternary assignment, or ``functools.partial`` decorator)
    to its donated positions — including one hop through wrapper methods
    that forward their own parameters into donated slots, and through
    factory methods that *return* a donating jit — then flags any read
    of a donated binding after the call in the same scope (LGB009),
    plus any single call passing one binding to BOTH a donated and a
    non-donated position (aliased donation: the runtime either copies,
    silently un-donating, or consumes the alias).
  * **donation silently dropped** — donation is a *compile option*, not
    part of the jaxpr; a refactor that rebuilds the jit without
    ``donate_argnums`` (or a platform that declines the alias) loses the
    PR 12 win with zero test signal.  :func:`check_hlo_aliasing` lowers
    each designated donating program and asserts the compiled HLO
    carries ``input_output_alias`` — the gate's runtime proof that the
    donation survived all the way through XLA.
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .common import Finding, PKG_ROOT, apply_allowlist, load_allowlist, \
    rel_file

# -- donating-callable discovery ----------------------------------------------


def _donate_argnums(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """The ``donate_argnums`` of a ``jax.jit(...)`` /
    ``functools.partial(jax.jit, ...)`` call node, or None."""
    name = ""
    f = call.func
    if isinstance(f, ast.Attribute):
        name = f.attr
    elif isinstance(f, ast.Name):
        name = f.id
    if name == "partial":
        inner = call.args[0] if call.args else None
        target = inner.attr if isinstance(inner, ast.Attribute) else (
            inner.id if isinstance(inner, ast.Name) else "")
        if target != "jit":
            return None
    elif name != "jit":
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Tuple):
            nums = []
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    nums.append(e.value)
            return tuple(nums)
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
    return None


def _target_name(node: ast.expr) -> str:
    """Bare name an assignment binds: ``self._jit_fused`` -> ``_jit_fused``."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _iter_functions(tree: ast.Module):
    """(name, node) for every function at any nesting depth."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node.name, node


def collect_donators(trees: Sequence[Tuple[str, ast.Module]]
                     ) -> Dict[str, Set[int]]:
    """Bare callable name -> donated positional indices, package-wide.

    Three layers, each one AST sweep:

    1. direct sites — ``X = jax.jit(fn, donate_argnums=...)`` (either arm
       of a ternary) and ``@functools.partial(jax.jit, ...,
       donate_argnums=...)`` decorators;
    2. factories — a method whose ``return`` yields a known donating
       binding (``_fused_iter_fn`` returning ``self._jit_fused``): a
       call of its RESULT donates at the same positions;
    3. wrappers — a method that forwards its own positional parameters
       into donated slots of a known donator (``train_async`` passing
       ``grad, hess`` into ``self._jit_tree_w``): callers of the wrapper
       donate at the corresponding parameter positions (``self``
       excluded, defaulted trailing params never marked).
    """
    donators: Dict[str, Set[int]] = {}
    # layer 1: direct jit sites
    for _, tree in trees:
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                values = [node.value]
                if isinstance(node.value, ast.IfExp):
                    values = [node.value.body, node.value.orelse]
                for v in values:
                    if not isinstance(v, ast.Call):
                        continue
                    nums = _donate_argnums(v)
                    if not nums:
                        continue
                    for tgt in node.targets:
                        name = _target_name(tgt)
                        if name:
                            donators.setdefault(name, set()).update(nums)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        nums = _donate_argnums(dec)
                        if nums:
                            donators.setdefault(node.name,
                                                set()).update(nums)
    # layer 2: factories returning a donating binding
    for _, tree in trees:
        for fname, fn in _iter_functions(tree):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Return) or node.value is None:
                    continue
                ret = _target_name(node.value)
                if ret in donators:
                    donators.setdefault(fname, set()).update(donators[ret])
    # layer 3: wrappers forwarding parameters into donated slots
    wrappers: Dict[str, Set[int]] = {}
    for _, tree in trees:
        for fname, fn in _iter_functions(tree):
            params = [a.arg for a in fn.args.args]
            offset = 1 if params[:1] == ["self"] else 0
            callable_params = params[offset:]
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = _target_name(node.func)
                nums = donators.get(callee)
                if not nums:
                    continue
                for pos in nums:
                    if pos >= len(node.args):
                        continue
                    arg = node.args[pos]
                    if isinstance(arg, ast.Name) and \
                            arg.id in callable_params:
                        wrappers.setdefault(fname, set()).add(
                            callable_params.index(arg.id))
    for name, nums in wrappers.items():
        donators.setdefault(name, set()).update(nums)
    return donators


# -- per-scope use-after-donate checking --------------------------------------


def _expr_text(node: ast.expr) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


def _donating_calls(fn: ast.AST, donators: Dict[str, Set[int]]):
    """(call, donated positions adjusted for boundness) in ``fn``.  A
    ``self.method(...)`` / ``obj.method(...)`` call binds the receiver,
    so the AST positions equal the donator's recorded positions for
    methods discovered via their jit-binding name (the jit wraps the
    unbound callable only when decorated — handled per sweep below)."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        callee = _target_name(node.func)
        nums = donators.get(callee)
        if nums:
            yield node, sorted(nums)
        elif isinstance(node.func, ast.Call):
            # factory-result call: self._fused_iter_fn()(score, ...)
            inner = _target_name(node.func.func)
            nums = donators.get(inner)
            if nums:
                yield node, sorted(nums)


def check_scope(fn: ast.AST, qualname: str, rf: str,
                donators: Dict[str, Set[int]]) -> List[Finding]:
    findings: List[Finding] = []
    for call, nums in _donating_calls(fn, donators):
        texts: Dict[int, str] = {}
        for pos in nums:
            if pos < len(call.args):
                t = _expr_text(call.args[pos])
                if t:
                    texts[pos] = t
        # aliased donation: one binding at a donated AND another position
        for pos, t in texts.items():
            for j, other in enumerate(call.args):
                # a donated pair reports once, from its lower position
                if j == pos or (j in texts and j < pos):
                    continue
                if _expr_text(other) == t:
                    findings.append(Finding(
                        "donation", "LGB009-use-after-donate", rf,
                        f"{t!r} passed to donated position {pos} AND "
                        f"position {j} of the same call — the aliased "
                        f"buffer is either copied (donation silently "
                        f"dropped) or consumed out from under the other "
                        f"argument; pass distinct buffers",
                        line=call.lineno, symbol=qualname))
                    break
        # use-after-donate: a read of the donated binding later in the
        # scope, before a rebinding kills it
        end = getattr(call, "end_lineno", call.lineno)
        for t in set(texts.values()):
            kill = None
            for node in ast.walk(fn):
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)) and \
                        node.lineno >= call.lineno:
                    targets = node.targets if isinstance(
                        node, ast.Assign) else [node.target]
                    flat: List[ast.expr] = []
                    for x in targets:
                        flat.extend(x.elts if isinstance(
                            x, (ast.Tuple, ast.List)) else [x])
                    if any(_expr_text(x) == t for x in flat):
                        if kill is None or node.lineno < kill:
                            kill = node.lineno
            for node in ast.walk(fn):
                if not isinstance(node, (ast.Name, ast.Attribute)):
                    continue
                if not isinstance(getattr(node, "ctx", None), ast.Load):
                    continue
                if node.lineno <= end or _expr_text(node) != t:
                    continue
                if kill is not None and node.lineno >= kill:
                    continue
                findings.append(Finding(
                    "donation", "LGB009-use-after-donate", rf,
                    f"{t!r} is donated at line {call.lineno} and read "
                    f"again at line {node.lineno} — a donated buffer is "
                    f"invalid after the call (the failure surfaces "
                    f"asynchronously, possibly iterations later); "
                    f"rebind before reuse",
                    line=node.lineno, symbol=qualname))
                break           # one finding per donated binding
    return findings


def _qualnames(tree: ast.Module) -> List[Tuple[str, ast.AST]]:
    out: List[Tuple[str, ast.AST]] = []

    def visit(node: ast.AST, stack: List[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append((".".join(stack + [child.name]), child))
                visit(child, stack + [child.name])
            elif isinstance(child, ast.ClassDef):
                visit(child, stack + [child.name])
            else:
                visit(child, stack)

    visit(tree, [])
    return out


def _package_trees(paths: Optional[Sequence[str]] = None
                   ) -> List[Tuple[str, ast.Module]]:
    if paths is None:
        paths = []
        for dirpath, dirnames, filenames in os.walk(PKG_ROOT):
            dirnames[:] = sorted(x for x in dirnames if x != "__pycache__")
            paths.extend(os.path.join(dirpath, f) for f in sorted(filenames)
                         if f.endswith(".py"))
    trees = []
    for p in paths:
        with open(p) as fh:
            trees.append((p, ast.parse(fh.read(), filename=p)))
    return trees


def use_after_donate(paths: Optional[Sequence[str]] = None
                     ) -> List[Finding]:
    """LGB009 findings package-wide (no allowlist applied)."""
    trees = _package_trees(paths)
    donators = collect_donators(trees)
    findings: List[Finding] = []
    for path, tree in trees:
        rf = rel_file(path)
        for qualname, fn in _qualnames(tree):
            findings.extend(check_scope(fn, qualname, rf, donators))
    return findings


# -- runtime donation-liveness assert -----------------------------------------

#: the designated donating programs: name -> (source file, min devices).
#: Each MUST lower with input->output aliasing in the compiled HLO when
#: tpu_donate_buffers is forced on — otherwise the PR 12 HBM win is
#: silently gone.
DONATING_PROGRAMS = {
    "learner_wave": ("lightgbm_tpu/learner_wave.py", 1),
    "feature_sharded": ("lightgbm_tpu/parallel/feature_sharded.py", 2),
    "gbdt_fused": ("lightgbm_tpu/boosting/gbdt.py", 1),
}

_ALIAS_MARK = "input_output_alias"


def _hlo_learner_wave() -> str:
    import jax
    import jax.numpy as jnp

    from ..config import Config
    from ..learner_wave import WaveTPUTreeLearner
    from .jaxpr_lint import _BASE_PARAMS, _toy_dataset

    params = dict(_BASE_PARAMS, tpu_donate_buffers="on")
    ds = _toy_dataset(512, 4, params)
    learner = WaveTPUTreeLearner(Config.from_params(params), ds.constructed)
    assert learner._donate, "tpu_donate_buffers=on did not engage"
    n = ds.constructed.num_data_padded
    g, h, b = (jnp.zeros(n, jnp.float32) for _ in range(3))
    fmask = jnp.ones(learner.num_features, bool)
    return learner._jit_tree_w.lower(
        learner.bins_packed(), g, h, b, fmask).compile().as_text()


def _hlo_feature_sharded() -> str:
    from ..config import Config
    from ..parallel.feature_sharded import FeatureShardedWaveLearner
    from ..parallel.mesh import make_mesh
    from .jaxpr_lint import _BASE_PARAMS, _toy_dataset

    params = dict(_BASE_PARAMS, enable_bundle=False,
                  tree_learner="feature", tpu_donate_buffers="on")
    ds = _toy_dataset(2048, 8, params)
    learner = FeatureShardedWaveLearner(
        Config.from_params(params), ds.constructed, make_mesh(2))
    assert learner._donate, "tpu_donate_buffers=on did not engage"
    return learner.lowered_hlo_text()


def _hlo_gbdt_fused() -> str:
    import jax.numpy as jnp

    import lightgbm_tpu as lgb

    from .jaxpr_lint import _BASE_PARAMS, _toy_dataset

    ds = _toy_dataset(512, 4, dict(_BASE_PARAMS))
    bst = lgb.Booster(dict(_BASE_PARAMS), ds)
    g = bst.gbdt
    assert g._can_fuse(), "fused gbdt step unavailable on this config"
    fn = g._fused_iter_fn()
    return fn.lower(
        g.train_score.score, g.learner.bins_packed(), g._bag_mask,
        g._feature_sample(), jnp.float32(0.1),
        g.objective.device_arrays()).compile().as_text()


_HLO_BUILDERS = {
    "learner_wave": _hlo_learner_wave,
    "feature_sharded": _hlo_feature_sharded,
    "gbdt_fused": _hlo_gbdt_fused,
}


def check_hlo_aliasing(names: Optional[Sequence[str]] = None
                       ) -> Tuple[List[Finding], Dict[str, str]]:
    """Lower + compile each designated donating program and assert the
    HLO text carries ``input_output_alias``.  Returns ``(findings,
    status)`` where status maps program -> "aliased" | skip reason."""
    import jax

    ndev = jax.device_count()
    findings: List[Finding] = []
    status: Dict[str, str] = {}
    for name, (file, min_dev) in sorted(DONATING_PROGRAMS.items()):
        if names is not None and name not in names:
            status[name] = "skipped: not selected by --programs"
            continue
        if ndev < min_dev:
            status[name] = f"skipped: needs {min_dev} devices, have {ndev}"
            continue
        text = _HLO_BUILDERS[name]()
        if _ALIAS_MARK in text:
            status[name] = "aliased"
        else:
            status[name] = "missing"
            findings.append(Finding(
                "donation", "donation-dropped", file,
                f"donating program {name!r} compiled WITHOUT "
                f"input->output aliasing — donate_argnums was lost (or "
                f"the platform declined it); the cross-iteration HBM "
                f"reuse is silently gone", symbol=name))
    return findings, status


# -- pass entry ---------------------------------------------------------------


def run(paths: Optional[Sequence[str]] = None,
        allowlist: Optional[Sequence[dict]] = None,
        with_hlo: bool = True,
        hlo_programs: Optional[Sequence[str]] = None):
    """The donation gate pass.  ``hlo_programs`` narrows the runtime
    asserts (None = all designated programs).  Returns ``(findings,
    suppressed, hlo_status)``."""
    if allowlist is None:
        allowlist = load_allowlist()
    findings = use_after_donate(paths)
    hlo_status: Dict[str, str] = {}
    if with_hlo:
        hlo_findings, hlo_status = check_hlo_aliasing(hlo_programs)
        findings += hlo_findings
    kept, suppressed = apply_allowlist(findings, allowlist)
    return kept, suppressed, hlo_status
