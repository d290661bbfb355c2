"""AST lint — PRNG/seed discipline and jit-body hygiene.

Pure-``ast`` (no jax import, runs in milliseconds), scanning the package,
``scripts/``, ``bench.py`` and ``__graft_entry__.py``. Tests are out of
scope (they pin seeds on purpose), as is ``staticcheck/fixtures.py``
(deliberately-bad seeded regressions). Rules, catalogued in
docs/STATIC_ANALYSIS.md:

  L1 prng-key-reuse     a ``jax.random.PRNGKey``/``key`` bound to a name
                        and consumed by more than one sampler call
                        without an intervening ``split``/``fold_in``
                        rebind — correlated streams, the classic
                        stateless-PRNG footgun
  L2 seed-offset-literal the replica-derivation constants 104729 / 7919
                        hardcoded anywhere but models/seeds.py — a
                        shadowed copy of the ``seed + r + 104729``
                        contract drifts silently when the canonical one
                        changes, and two call sites disagreeing on the
                        offset makes replica streams collide with solo
                        runs instead of reproducing them
  L3 numpy-in-jit       ``np.*`` / ``numpy.*`` calls inside a
                        jit-decorated function (or a function nested in
                        one): numpy either crashes on tracers or —
                        worse — silently constant-folds a value that was
                        meant to be traced
  L4 tracer-branch      ``if``/``while`` conditions that boolean-test a
                        non-static parameter of a jit-decorated
                        function (``is None`` structure tests and
                        ``.shape``/``.ndim``-style attribute tests are
                        trace-time static and allowed)
"""

from __future__ import annotations

import ast
import dataclasses
import os

from p2p_gossip_tpu.models.seeds import CHURN_SEED_OFFSET, LOSS_SEED_OFFSET

#: The canonical home of the replica seed-offset constants; literal
#: occurrences anywhere else are L2 violations. The values are IMPORTED
#: from that home so this linter never carries a shadow copy itself.
SEEDS_MODULE = os.path.join("p2p_gossip_tpu", "models", "seeds.py")
SEED_OFFSET_LITERALS = {LOSS_SEED_OFFSET, CHURN_SEED_OFFSET}

#: jax.random attrs that do NOT consume a key's uniqueness.
_KEY_SAFE_ATTRS = {
    "split", "fold_in", "key_data", "wrap_key_data", "clone", "key_impl",
}
_KEY_MAKERS = {"PRNGKey", "key"}

#: Files never scanned (relative to the repo root).
EXCLUDE_PARTS = (
    os.path.join("p2p_gossip_tpu", "staticcheck", "fixtures.py"),
    "tests" + os.sep,
)


@dataclasses.dataclass
class LintViolation:
    file: str
    line: int
    rule: str
    message: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"{self.file}:{self.line}: [{self.rule}] {self.message}"


def _attr_chain(node) -> list[str]:
    """['jax', 'random', 'uniform'] for jax.random.uniform; [] if not a
    plain name/attribute chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


def _is_random_call(call: ast.Call) -> list[str]:
    chain = _attr_chain(call.func)
    return chain if "random" in chain[:-1] else []


def _jit_decoration(fn: ast.FunctionDef):
    """(is_jitted, static_names) from the decorator list. Recognizes
    ``@jax.jit``, ``@jit``, and ``@functools.partial(jax.jit, ...)`` /
    ``@partial(jax.jit, ...)`` with literal ``static_argnames``."""
    for deco in fn.decorator_list:
        chain = _attr_chain(deco if not isinstance(deco, ast.Call) else deco.func)
        if chain and chain[-1] == "jit":
            return True, set()
        if isinstance(deco, ast.Call) and chain and chain[-1] == "partial":
            args = deco.args
            if args and _attr_chain(args[0])[-1:] == ["jit"]:
                statics: set[str] = set()
                for kw in deco.keywords:
                    if kw.arg in ("static_argnames", "static_argnums"):
                        for item in ast.walk(kw.value):
                            if isinstance(item, ast.Constant) and isinstance(
                                item.value, str
                            ):
                                statics.add(item.value)
                return True, statics
    return False, set()


def _names_in(node) -> set[str]:
    return {
        n.id for n in ast.walk(node) if isinstance(n, ast.Name)
    }


def _test_flags_param(test, params: set[str]) -> str | None:
    """The offending parameter name if ``test`` boolean-tests one of
    ``params`` in a way that calls ``__bool__`` on a tracer; None if the
    test is trace-time static (``is None``, attribute access, literals)."""
    if isinstance(test, ast.BoolOp):
        for operand in test.values:
            hit = _test_flags_param(operand, params)
            if hit:
                return hit
        return None
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _test_flags_param(test.operand, params)
    if isinstance(test, ast.Compare):
        # `x is None` / `x is not None` are structure tests, never traced.
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return None
        for side in [test.left] + list(test.comparators):
            if isinstance(side, ast.Name) and side.id in params:
                return side.id
        return None
    if isinstance(test, ast.Name) and test.id in params:
        return test.id
    # Attribute tests (x.ndim == 2), calls (isinstance), literals: static
    # at trace time or out of this rule's scope.
    return None


class _FileLinter:
    def __init__(self, path: str, rel: str, tree: ast.Module):
        self.path = path
        self.rel = rel
        self.tree = tree
        self.violations: list[LintViolation] = []

    def flag(self, node, rule: str, message: str) -> None:
        self.violations.append(
            LintViolation(self.rel, getattr(node, "lineno", 0), rule, message)
        )

    # -- L2 ---------------------------------------------------------------
    def lint_seed_literals(self) -> None:
        if self.rel.replace("/", os.sep).endswith(SEEDS_MODULE):
            return
        for node in ast.walk(self.tree):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, int)
                and not isinstance(node.value, bool)
                and node.value in SEED_OFFSET_LITERALS
            ):
                self.flag(
                    node, "seed-offset-literal",
                    f"hardcoded seed offset {node.value} shadows the "
                    "replica-derivation contract — use "
                    "p2p_gossip_tpu.models.seeds "
                    "(loss_stream_seed/churn_stream_seed)",
                )

    # -- L1 ---------------------------------------------------------------
    def lint_key_reuse(self) -> None:
        for fn in ast.walk(self.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._lint_key_reuse_scope(fn)

    def _lint_key_reuse_scope(self, fn) -> None:
        uses: dict[str, int] = {}

        class V(ast.NodeVisitor):
            def visit_FunctionDef(self, node):  # don't cross scopes
                if node is fn:
                    self.generic_visit(node)

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_Assign(self, node, outer=self):
                chain = (
                    _is_random_call(node.value)
                    if isinstance(node.value, ast.Call)
                    else []
                )
                for tgt in node.targets:
                    for name_node in ast.walk(tgt):
                        if isinstance(name_node, ast.Name):
                            if chain and chain[-1] in (
                                _KEY_MAKERS | _KEY_SAFE_ATTRS
                            ):
                                # Fresh key or split/fold_in product:
                                # (re)arm the one-use budget.
                                uses[name_node.id] = 0
                            else:
                                # Rebound to something else: stop tracking.
                                uses.pop(name_node.id, None)
                self.generic_visit(node)

            def visit_Call(self, node, outer=self):
                chain = _is_random_call(node)
                if chain and chain[-1] not in (
                    _KEY_SAFE_ATTRS | _KEY_MAKERS
                ):
                    for arg in list(node.args) + [
                        kw.value for kw in node.keywords
                    ]:
                        if isinstance(arg, ast.Name) and arg.id in uses:
                            uses[arg.id] += 1
                            if uses[arg.id] > 1:
                                outer.flag(
                                    node, "prng-key-reuse",
                                    f"key '{arg.id}' consumed by more than "
                                    "one sampler without split()/fold_in() "
                                    "— streams are identical, not "
                                    "independent",
                                )
                self.generic_visit(node)

        V().visit(fn)

    # -- L3 / L4 -----------------------------------------------------------
    def lint_jit_bodies(self) -> None:
        for fn in ast.walk(self.tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            jitted, statics = _jit_decoration(fn)
            if not jitted:
                continue
            params = {
                a.arg
                for a in (
                    fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
                )
            } - statics
            self._lint_jit_body(fn, params)

    def _lint_jit_body(self, fn, traced_params: set[str]) -> None:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if chain and chain[0] in ("np", "numpy"):
                    self.flag(
                        node, "numpy-in-jit",
                        f"numpy call {'.'.join(chain)}() inside jitted "
                        f"'{fn.name}' — use jnp (numpy crashes on tracers "
                        "or silently constant-folds)",
                    )
            if isinstance(node, (ast.If, ast.While)):
                hit = _test_flags_param(node.test, traced_params)
                if hit:
                    self.flag(
                        node, "tracer-branch",
                        f"Python branch on traced parameter '{hit}' inside "
                        f"jitted '{fn.name}' — trace-time branching needs "
                        "a static arg (static_argnames) or lax.cond/select",
                    )
            if isinstance(node, ast.IfExp):
                hit = _test_flags_param(node.test, traced_params)
                if hit:
                    self.flag(
                        node, "tracer-branch",
                        f"conditional expression on traced parameter "
                        f"'{hit}' inside jitted '{fn.name}' — needs a "
                        "static arg or jnp.where",
                    )


def _scan_roots(repo_root: str) -> list[str]:
    roots = []
    for sub in ("p2p_gossip_tpu", "scripts"):
        base = os.path.join(repo_root, sub)
        for dirpath, _dirs, files in os.walk(base):
            for f in sorted(files):
                if f.endswith(".py"):
                    roots.append(os.path.join(dirpath, f))
    for f in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
        path = os.path.join(repo_root, f)
        if os.path.exists(path):
            roots.append(path)
    return roots


def lint_file(path: str, rel: str | None = None) -> list[LintViolation]:
    rel = rel or path
    with open(path, encoding="utf-8") as f:
        src = f.read()
    return lint_source(src, rel)


def lint_source(src: str, rel: str) -> list[LintViolation]:
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [LintViolation(rel, e.lineno or 0, "syntax-error", str(e))]
    linter = _FileLinter(rel, rel, tree)
    linter.lint_seed_literals()
    linter.lint_key_reuse()
    linter.lint_jit_bodies()
    return linter.violations


def run_lint(repo_root: str | None = None) -> dict:
    """Lint the repo; JSON-ready {"ok", "files_scanned", "violations"}."""
    if repo_root is None:
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    violations: list[LintViolation] = []
    scanned = 0
    for path in _scan_roots(repo_root):
        rel = os.path.relpath(path, repo_root)
        if any(part in rel + ("" if rel.endswith(".py") else os.sep)
               for part in EXCLUDE_PARTS):
            continue
        scanned += 1
        violations.extend(lint_file(path, rel))
    return {
        "ok": not violations,
        "files_scanned": scanned,
        "violations": [v.as_dict() for v in violations],
    }
