"""AST-based repo lint: the standing source rules as machine checks.

Replaces the three ``grep -E`` gates that used to live in
``scripts/verify.sh`` (compat-import, private-backend, removed-wrapper)
and adds two rules greps could not express without false positives:

- ``compat-import``     drift-prone JAX APIs (shard_map, CompilerParams,
                        pallas tpu import, lax.axis_size, ``jax.core.*``)
                        must route through ``repro.compat``.
- ``private-backend``   ``repro.core.overlap``'s underscore backends are an
                        implementation detail; call ``FusedOp`` / the
                        ``*_ref`` oracles.
- ``removed-wrapper``   the pre-FusedOp wrappers (``ag_matmul``,
                        ``matmul_rs``, ``matmul_ar``) no longer exist —
                        the AST sees CALLS, so the ``*_ref`` oracles and
                        string literals in subprocess-driving tests no
                        longer trip it (both were grep escapes).
- ``raw-collective``    raw ``lax.ppermute`` / ``lax.all_gather`` /
                        ``lax.all_to_all`` / ``lax.psum_scatter`` calls
                        belong to the seam layer (``core/overlap.py``,
                        ``parallel/sharding.py``); anywhere else they are
                        invisible to the seam census.  (all_to_all and
                        psum_scatter were blind spots until the MoE a2a
                        seam landed — exactly the transports the EP
                        exchange and the ZeRO-1 reduce use.)
- ``bare-shard-map``    ``shard_map`` obtained from ``jax`` directly
                        instead of ``repro.compat`` (signature moved
                        across jax versions).
- ``deprecated-q8-mode`` the legacy ``*_q8`` mode spellings ("xla_q8",
                        "decomposed_q8") are a compatibility shim — spell
                        the wire as ``wire_dtype="int8"`` on the base mode
                        instead.  Docstring constants are exempt (prose may
                        document the deprecation); ``core/overlap.py`` owns
                        the shim itself.
- ``stale-allow``       a ``# lint: allow(<rule>)`` escape that suppresses
                        NOTHING (the violation moved or was fixed, or the
                        rule name is unknown).  Stale escapes rot silently
                        as code moves and then mask real violations later;
                        each one is reported at its comment line.

Per-line escape: ``# lint: allow(<rule>)`` on the offending line or the
line directly above it.  Escapes are extracted from real COMMENT tokens
(``tokenize``), so escape-shaped text inside string literals — docstrings,
subprocess source in tests — neither suppresses a finding nor counts as a
stale escape.
"""
from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path
from typing import List, Optional, Sequence, Set, Tuple

RULES = ("compat-import", "private-backend", "removed-wrapper",
         "raw-collective", "bare-shard-map", "deprecated-q8-mode",
         "stale-allow")

LINT_SCOPE = ("src", "benchmarks", "examples", "tests")

# files exempt per rule (relative path substrings)
_ALLOWED = {
    "compat-import": ("src/repro/compat/",),
    "private-backend": ("src/repro/core/overlap.py",),
    "removed-wrapper": (),
    "raw-collective": ("src/repro/core/overlap.py",
                       "src/repro/parallel/sharding.py"),
    "bare-shard-map": ("src/repro/compat/",),
    "deprecated-q8-mode": ("src/repro/core/overlap.py",),
    "stale-allow": (),
}

_PRIVATE_BACKENDS = {
    "_ag_ring", "_ag_bidir", "_rs_ring", "_rs_bidir", "_rs_core",
    "_ar_core", "_ar_ring_quant", "_fused_impl", "_fused_ag", "_fused_bwd",
    "_gather_full", "_ring_gather", "_q8_encode", "_q8_decode",
    "_wire_hop", "_int4_pack", "_int4_unpack",
}
# built without spelling the deprecated suffix as one literal (this file
# lints itself)
_Q8_SUFFIX = "_q" + "8"
_Q8_BASES = ("xla", "decomposed")
_PRIVATE_BACKEND_RE = re.compile(
    r"^_(ag_matmul|matmul_ar|matmul_rs)_(xla|decomposed|bidir|flux|impl)")
_REMOVED_WRAPPERS = {"ag_matmul", "matmul_rs", "matmul_ar"}
_RAW_COLLECTIVES = {"ppermute", "all_gather", "all_to_all", "psum_scatter"}
_COMPILER_PARAMS = {"CompilerParams"}
_ESCAPE_RE = re.compile(r"#\s*lint:\s*allow\(([a-z0-9-]+(?:\s*,\s*[a-z0-9-]+)*)\)")


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _is_jax_core(module: str) -> bool:
    return module == "jax.core" or module.startswith("jax.core.")


def _is_private_backend(name: str) -> bool:
    return name in _PRIVATE_BACKENDS or bool(_PRIVATE_BACKEND_RE.match(name))


def _escape_comments(source: str) -> List[Tuple[int, Set[str]]]:
    """One ``(line, {rules})`` entry per ACTUAL escape comment.

    Extracted from ``tokenize`` COMMENT tokens so escape-shaped text inside
    string literals (docstrings, subprocess source embedded in tests) is
    invisible — it neither suppresses a finding nor shows up as a stale
    escape.  Unparseable sources fall back to the line regex (the AST pass
    reports them separately anyway)."""
    entries: List[Tuple[int, Set[str]]] = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                m = _ESCAPE_RE.search(tok.string)
                if m:
                    entries.append((tok.start[0],
                                    {r.strip()
                                     for r in m.group(1).split(",")}))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        for i, text in enumerate(source.splitlines(), start=1):
            m = _ESCAPE_RE.search(text)
            if m:
                entries.append((i, {r.strip()
                                    for r in m.group(1).split(",")}))
    return entries


def _escapes(source: str):
    """line -> set of escaped rules (an escape covers its line AND the
    next one, so it can sit above a long call)."""
    out: dict = {}
    for i, rules in _escape_comments(source):
        out.setdefault(i, set()).update(rules)
        out.setdefault(i + 1, set()).update(rules)
    return out


class _Visitor(ast.NodeVisitor):
    def __init__(self, relpath: str):
        self.relpath = relpath
        self.found: List[Violation] = []
        self._doc_nodes: Set[int] = set()

    def _hit(self, node, rule: str, message: str):
        if any(a in self.relpath for a in _ALLOWED.get(rule, ())):
            return
        self.found.append(Violation(self.relpath, node.lineno, rule, message))

    # ---- docstrings (exempt from the constant rules) ----------------------
    def _mark_docstring(self, node):
        body = getattr(node, "body", [])
        if (body and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)):
            self._doc_nodes.add(id(body[0].value))

    def visit_Module(self, node):
        self._mark_docstring(node)
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        self._mark_docstring(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node):
        self._mark_docstring(node)
        self.generic_visit(node)

    def visit_ClassDef(self, node):
        self._mark_docstring(node)
        self.generic_visit(node)

    # ---- imports ----------------------------------------------------------
    def visit_Import(self, node):
        for alias in node.names:
            if alias.name.startswith("jax") and (
                    alias.name.endswith(".shard_map")
                    or _is_jax_core(alias.name)):
                self._hit(node, "compat-import",
                          f"import {alias.name} — use repro.compat")
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        mod = node.module or ""
        names = {a.name for a in node.names}
        if mod.startswith("jax") and (mod.endswith(".shard_map")
                                      or "shard_map" in names):
            rule = ("bare-shard-map" if mod == "jax"
                    else "compat-import")
            self._hit(node, rule,
                      f"shard_map imported from {mod!r} — use "
                      "repro.compat.shard_map")
        if _is_jax_core(mod) or (mod == "jax" and "core" in names):
            self._hit(node, "compat-import",
                      f"jax.core imported from {mod!r} — use repro.compat")
        if mod.startswith("jax.experimental.pallas") and "tpu" in names:
            self._hit(node, "compat-import",
                      "pallas tpu backend import — use repro.compat.pltpu")
        if names & _COMPILER_PARAMS:
            self._hit(node, "compat-import",
                      "CompilerParams import — use "
                      "repro.compat.compiler_params")
        if mod == "repro.core.overlap" or mod.endswith(".core.overlap"):
            for a in node.names:
                if _is_private_backend(a.name):
                    self._hit(node, "private-backend",
                              f"import of private backend {a.name!r} from "
                              "repro.core.overlap")
        self.generic_visit(node)

    # ---- attributes -------------------------------------------------------
    def visit_Attribute(self, node):
        base = node.value
        base_name = base.id if isinstance(base, ast.Name) else (
            base.attr if isinstance(base, ast.Attribute) else None)
        if node.attr == "shard_map" and base_name == "jax":
            self._hit(node, "bare-shard-map",
                      "jax.shard_map — use repro.compat.shard_map")
        if node.attr in _COMPILER_PARAMS:
            self._hit(node, "compat-import",
                      f"{node.attr} attribute — use "
                      "repro.compat.compiler_params")
        if (node.attr == "core" and isinstance(base, ast.Name)
                and base.id == "jax"):
            self._hit(node, "compat-import",
                      "jax.core — use repro.compat")
        if node.attr == "axis_size" and base_name == "lax":
            self._hit(node, "compat-import",
                      "lax.axis_size — use repro.compat.axis_size")
        if base_name == "overlap" and _is_private_backend(node.attr):
            self._hit(node, "private-backend",
                      f"overlap.{node.attr} — private backend; go through "
                      "FusedOp")
        self.generic_visit(node)

    # ---- calls ------------------------------------------------------------
    def visit_Call(self, node):
        fn = node.func
        name = None
        base_name = None
        if isinstance(fn, ast.Name):
            name = fn.id
        elif isinstance(fn, ast.Attribute):
            name = fn.attr
            b = fn.value
            base_name = b.id if isinstance(b, ast.Name) else (
                b.attr if isinstance(b, ast.Attribute) else None)
        if name in _REMOVED_WRAPPERS:
            self._hit(node, "removed-wrapper",
                      f"call to removed wrapper {name!r} — use "
                      "overlap.FusedOp (or the *_ref oracle)")
        if name in _RAW_COLLECTIVES and base_name in ("lax", "jax"):
            self._hit(node, "raw-collective",
                      f"raw {base_name}.{name} outside the seam layer — "
                      "route through core/overlap.py or "
                      "parallel/sharding.py (or tag + escape)")
        self.generic_visit(node)

    # ---- constants --------------------------------------------------------
    def visit_Constant(self, node):
        v = node.value
        if (isinstance(v, str) and v.endswith(_Q8_SUFFIX)
                and v[:-len(_Q8_SUFFIX)] in _Q8_BASES
                and id(node) not in self._doc_nodes):
            base = v[:-len(_Q8_SUFFIX)]
            self._hit(node, "deprecated-q8-mode",
                      f"deprecated mode spelling {v!r} — use "
                      f"mode={base!r} with wire_dtype='int8'")
        self.generic_visit(node)


def _stale_escape_violations(relpath: str, source: str,
                             raw: List[Violation]) -> List[Violation]:
    """``stale-allow``: escape comments that suppress nothing.

    An escape rule at comment line ``i`` is USED iff some raw finding of
    that rule sits on line ``i`` or ``i+1`` (the escape's coverage
    window).  Unknown rule names are always stale — they can never
    suppress anything.  ``stale-allow`` itself is exempt from the
    staleness check (it exists only to suppress findings OF this rule,
    which are emitted at the comment line and filtered by the normal
    escape pass)."""
    hit_lines = {(f.line, f.rule) for f in raw}
    out: List[Violation] = []
    for line, rules in _escape_comments(source):
        for rule in sorted(rules):
            if rule == "stale-allow":
                continue
            if rule not in RULES:
                out.append(Violation(
                    relpath, line, "stale-allow",
                    f"# lint: allow({rule}) names an unknown rule — "
                    f"known rules: {', '.join(RULES)}"))
            elif not ((line, rule) in hit_lines
                      or (line + 1, rule) in hit_lines):
                out.append(Violation(
                    relpath, line, "stale-allow",
                    f"# lint: allow({rule}) suppresses no {rule} "
                    "violation — stale escape; remove it"))
    return out


def lint_source(source: str, relpath: str) -> List[Violation]:
    try:
        tree = ast.parse(source, filename=relpath)
    except SyntaxError as e:
        return [Violation(relpath, e.lineno or 0, "compat-import",
                          f"unparseable: {e.msg}")]
    v = _Visitor(relpath)
    v.visit(tree)
    esc = _escapes(source)
    found = v.found + _stale_escape_violations(relpath, source, v.found)
    return [f for f in found if f.rule not in esc.get(f.line, ())]


def lint_file(path: Path, root: Path) -> List[Violation]:
    rel = str(path.relative_to(root))
    return lint_source(path.read_text(), rel)


def lint_tree(root: Optional[Path] = None,
              scope: Sequence[str] = LINT_SCOPE) -> List[Violation]:
    root = Path(root) if root else _repo_root()
    out: List[Violation] = []
    for top in scope:
        base = root / top
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*.py")):
            out.extend(lint_file(path, root))
    return out


def _repo_root() -> Path:
    # src/repro/analysis/lint.py -> repo root
    return Path(__file__).resolve().parents[3]
