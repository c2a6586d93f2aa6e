"""R7 + R8: the two contract lints — telemetry vs doc, knobs vs registry.

**R7 telemetry contract.** ``doc/observability.md`` is not prose: the
fleet aggregator sums families by NAME, the SLO engine selects its
series by NAME, and dashboards join on LABELS. A family emitted
but not documented silently vanishes from all three; a documented row
whose emitter was deleted leaves dashboards graphing flatlines. R7
diffs the two worlds both ways and checks label sets (code labels must
be a subset of the documented ones). Span stage names get the same
treatment against the doc's stage tables.

Code-side extraction is purely syntactic and covers the repo's three
emission idioms:

* ``REGISTRY.counter/gauge/histogram("fishnet_x", help, labelnames=..)``
  and direct ``Counter/Gauge/Histogram("fishnet_x", ...)`` construction
* ``counter_family/gauge_family("fishnet_x", help, v, labels={...})``
* ``MetricFamily("fishnet_x", "gauge", ...)`` / ``Sample("fishnet_x",
  v, {"label": ...})`` hand-built exposition (fleet/cost/slo planes)
* declarative spec tuples ``("fishnet_x", "gauge", help)`` (the
  ``_COUNTER_METRICS`` table idiom in ``search/service.py``) and local
  builder helpers called with a literal family as FIRST argument
* stages: ``<SPANS-ish receiver>.record("stage", ...)``, including a
  module-constant stage name (``RECOVER_STAGE``)

**R8 escape-hatch registry.** Every ``FISHNET_*`` env read, every
``--option`` in the product argparser (``configure.py``) and every
``fishnet.ini`` key must have a row in
:mod:`fishnet_tpu.analysis.registry` — see that module's docstring for
the contract. Declared-but-unused rows and dangling
``documented_in``/``tested_by`` pointers are findings too.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from fishnet_tpu.analysis.engine import Finding, Module, Project

# =========================================================================
# R7
# =========================================================================

_FAMILY_RE = re.compile(r"^fishnet_[a-z0-9_]+$")
_DOC_TOKEN_RE = re.compile(r"`(fishnet_[a-z0-9_]+)(\{[^`}]*\})?[^`]*`")
def _brace_keys(body: str) -> List[str]:
    """Label keys from a ``{...}`` doc mention: ``{slo,window}`` and
    ``{scope="prewire",family="az"}`` both work."""
    out = []
    for part in body.strip("{}").split(","):
        key = part.split("=", 1)[0].strip().strip("\"'`")
        if re.fullmatch(r"[a-z0-9_]+", key):
            out.append(key)
    return out
_BACKTICK_RE = re.compile(r"`([^`]+)`")
_INSTRUMENT_METHODS = ("counter", "gauge", "histogram")
_FAMILY_HELPERS = ("counter_family", "gauge_family")


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class _Emission:
    def __init__(self, name: str, path: str, line: int, col: int,
                 labels: Optional[Set[str]] = None):
        self.name = name
        self.path = path
        self.line = line
        self.col = col
        self.labels = labels or set()


def _code_families(project: Project) -> List[_Emission]:
    out: List[_Emission] = []
    for mod in project.modules.values():
        if mod.name.startswith("fishnet_tpu.analysis"):
            continue  # the checker's own fixtures/specs are not emitters
        path = str(mod.path)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                em = _call_emission(node, path)
                if em is not None:
                    out.append(em)
            elif isinstance(node, ast.Tuple) and len(node.elts) >= 2:
                name = _str_const(node.elts[0])
                kind = _str_const(node.elts[1])
                if (
                    name is not None and _FAMILY_RE.match(name)
                    and kind in _INSTRUMENT_METHODS
                ):
                    out.append(
                        _Emission(name, path, node.lineno, node.col_offset)
                    )
    return out


_INSTRUMENT_CLASSES = ("Counter", "Gauge", "Histogram")


def _kwarg(node: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _call_emission(node: ast.Call, path: str) -> Optional[_Emission]:
    func = node.func
    method = None
    if isinstance(func, ast.Attribute):
        method = func.attr
    elif isinstance(func, ast.Name):
        method = func.id
    if method is None:
        return None
    name = _str_const(node.args[0]) if node.args else None
    if name is None:
        kw_name = _kwarg(node, "name")
        name = _str_const(kw_name) if kw_name is not None else None
    if name is None or not _FAMILY_RE.match(name):
        return None
    labels: Set[str] = set()
    if method in _INSTRUMENT_METHODS or method in _INSTRUMENT_CLASSES:
        ln = _kwarg(node, "labelnames")
        if isinstance(ln, (ast.Tuple, ast.List)):
            for elt in ln.elts:
                lab = _str_const(elt)
                if lab is not None:
                    labels.add(lab)
        return _Emission(name, path, node.lineno, node.col_offset, labels)
    if method in _FAMILY_HELPERS:
        lv = _kwarg(node, "labels")
        if isinstance(lv, ast.Dict):
            for key in lv.keys:
                lab = _str_const(key) if key is not None else None
                if lab is not None:
                    labels.add(lab)
        return _Emission(name, path, node.lineno, node.col_offset, labels)
    if method == "Sample":
        lv = _kwarg(node, "labels")
        if lv is None and len(node.args) >= 3:
            lv = node.args[2]
        if isinstance(lv, ast.Dict):
            for key in lv.keys:
                lab = _str_const(key) if key is not None else None
                if lab is not None:
                    labels.add(lab)
        return _Emission(name, path, node.lineno, node.col_offset, labels)
    if method == "MetricFamily":
        return _Emission(name, path, node.lineno, node.col_offset)
    if isinstance(func, ast.Name) and node.args and _str_const(
        node.args[0]
    ) == name:
        # Local builder helper called with a literal family name first
        # (the cost plane's `fam("fishnet_x", help, values, label)`).
        return _Emission(name, path, node.lineno, node.col_offset)
    return None


def _receiver_text(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _module_str_constants(project: Project) -> Dict[str, Dict[str, str]]:
    """Module-level ``NAME = "literal"`` tables, for stage constants."""
    out: Dict[str, Dict[str, str]] = {}
    for mod in project.modules.values():
        table: Dict[str, str] = {}
        for stmt in mod.tree.body:
            if (
                isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
            ):
                value = _str_const(stmt.value)
                if value is not None:
                    table[stmt.targets[0].id] = value
        out[mod.name] = table
    return out


def _code_stages(project: Project) -> List[_Emission]:
    consts = _module_str_constants(project)
    out: List[_Emission] = []
    for mod in project.modules.values():
        if mod.name.startswith("fishnet_tpu.analysis"):
            continue
        path = str(mod.path)
        for node in ast.walk(mod.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "record"
                and node.args
            ):
                continue
            recv = _receiver_text(node.func.value).upper()
            if "SPANS" not in recv and "RECORDER" not in recv:
                continue
            arg = node.args[0]
            stage = _str_const(arg)
            if stage is None and isinstance(arg, ast.Name):
                dotted = project.resolve_dotted(arg, mod.imports)
                if dotted is not None and "." in dotted:
                    owner, _, const = dotted.rpartition(".")
                    stage = consts.get(owner, {}).get(const)
                if stage is None:
                    stage = consts.get(mod.name, {}).get(arg.id)
            if stage is not None:
                out.append(
                    _Emission(stage, path, node.lineno, node.col_offset)
                )
    return out


class _DocContract:
    """Parsed view of doc/observability.md."""

    def __init__(self, path: Path):
        self.path = path
        self.mentioned: Set[str] = set()  # any backticked fishnet_* token
        self.declared: Dict[str, int] = {}  # table-row family -> doc line
        self.labels: Dict[str, Set[str]] = {}
        self.stages: Dict[str, int] = {}  # stage table rows -> doc line
        self._parse()

    def _parse(self) -> None:
        lines = self.path.read_text(encoding="utf-8").splitlines()
        header_cells: List[str] = []
        for lineno, line in enumerate(lines, start=1):
            for m in _DOC_TOKEN_RE.finditer(line):
                name = m.group(1)
                if name == "fishnet_tpu" or name.endswith("_"):
                    continue
                self.mentioned.add(name)
                if m.group(2):
                    self.labels.setdefault(name, set()).update(
                        _brace_keys(m.group(2))
                    )
            stripped = line.strip()
            if not stripped.startswith("|"):
                header_cells = []
                continue
            cells = [c.strip() for c in stripped.strip("|").split("|")]
            if cells and cells[0] in ("Name", "Stage"):
                header_cells = cells
                continue
            if not header_cells or set(cells[0]) <= {"-", " ", ":"}:
                continue
            first = _BACKTICK_RE.match(cells[0])
            if first is None:
                continue
            token = first.group(1)
            if header_cells[0] == "Stage":
                self.stages.setdefault(token, lineno)
                continue
            m = _DOC_TOKEN_RE.match(cells[0])
            if m is None:
                continue
            name = m.group(1)
            self.declared.setdefault(name, lineno)
            labs = self.labels.setdefault(name, set())
            # Label names can sit in a dedicated Labels cell, in parens
            # next to the type, or in the Meaning prose ("labels
            # `backend`, `psqt_path` carry static config") — accept any
            # word-like backticked token in the row. Over-collection
            # only relaxes the subset check; it can't fabricate a
            # finding.
            for cell in cells[1:]:
                labs.update(
                    tok for tok in _BACKTICK_RE.findall(cell)
                    if re.fullmatch(r"[a-z0-9_]+", tok)
                )


class TelemetryContractRule:
    """R7 — metric families and span stages must match
    doc/observability.md, both directions, labels included."""

    id = "R7"
    name = "telemetry-contract"

    def __init__(self, doc_path: Optional[Path] = None):
        self._doc_path = doc_path

    def _resolve_doc(self, project: Project) -> Optional[Path]:
        if self._doc_path is not None:
            return self._doc_path if self._doc_path.exists() else None
        for mod in project.modules.values():
            if mod.name.startswith("fishnet_tpu."):
                for parent in Path(mod.path).resolve().parents:
                    cand = parent / "doc" / "observability.md"
                    if cand.exists():
                        return cand
        return None

    def check(self, project: Project) -> Iterator[Finding]:
        doc = self._resolve_doc(project)
        if doc is None:
            return  # nothing to check against (doc-less fixture run)
        contract = _DocContract(doc)
        families = _code_families(project)
        stages = _code_stages(project)
        out: List[Finding] = []
        emitted = {em.name for em in families}
        for em in sorted(families, key=lambda e: (e.path, e.line, e.name)):
            if em.name not in contract.mentioned:
                out.append(Finding(
                    rule=self.id, path=em.path, line=em.line, col=em.col,
                    message=(
                        f"metric family `{em.name}` is emitted here but "
                        f"has no row in {contract.path.name} — the fleet "
                        "aggregator, the SLO engine, and every "
                        "dashboard are blind to it"
                    ),
                    suggestion=(
                        "add a Name/Type/Labels/Meaning row to the "
                        "matching table in doc/observability.md"
                    ),
                ))
                continue
            doc_labels = contract.labels.get(em.name, set())
            extra = em.labels - doc_labels
            if extra:
                out.append(Finding(
                    rule=self.id, path=em.path, line=em.line, col=em.col,
                    message=(
                        f"`{em.name}` is emitted with label(s) "
                        + ", ".join(f"`{x}`" for x in sorted(extra))
                        + f" not documented in {contract.path.name} "
                        f"(documented: {sorted(doc_labels) or 'none'})"
                    ),
                    suggestion=(
                        "document the label in the family's row — label "
                        "drift breaks every aggregation that sums over it"
                    ),
                ))
        for name, lineno in sorted(contract.declared.items()):
            if name not in emitted:
                out.append(Finding(
                    rule=self.id, path=str(contract.path), line=lineno,
                    col=0,
                    message=(
                        f"documented metric family `{name}` has no "
                        "emitter left in the tree — dashboards built on "
                        "this row graph a flatline"
                    ),
                    suggestion=(
                        "delete the doc row, or restore the emitter it "
                        "described"
                    ),
                ))
        emitted_stages = {em.name for em in stages}
        for em in sorted(stages, key=lambda e: (e.path, e.line, e.name)):
            if em.name not in contract.stages:
                out.append(Finding(
                    rule=self.id, path=em.path, line=em.line, col=em.col,
                    message=(
                        f"span stage `{em.name}` is recorded here but "
                        f"missing from the stage tables in "
                        f"{contract.path.name} — stage names are a "
                        "stable contract (the critical-path and span "
                        "tooling key on them)"
                    ),
                    suggestion="add a Stage/Recorded in/Covers row",
                ))
        for name, lineno in sorted(contract.stages.items()):
            if name not in emitted_stages:
                out.append(Finding(
                    rule=self.id, path=str(contract.path), line=lineno,
                    col=0,
                    message=(
                        f"documented span stage `{name}` is never "
                        "recorded in the tree"
                    ),
                    suggestion="delete the stage row or restore the span",
                ))
        yield from out


# =========================================================================
# R8
# =========================================================================

_ENV_NAME_RE = re.compile(r"^FISHNET_[A-Z0-9_]+$")
_INI_KEY_RE = re.compile(r"^[A-Z][A-Za-z0-9]+$")
_ENV_CALLS = ("environ.get", "environ.setdefault", "environ.pop", "getenv")
#: modules whose argparse / ini surface is the PRODUCT contract (aux
#: tools like telemetry/fleet.py own their flags).
_CLI_SCOPE = ("fishnet_tpu.configure",)


class _Usage:
    def __init__(self, name: str, kind: str, path: str, line: int, col: int,
                 aliases: Tuple[str, ...] = ()):
        self.name = name
        self.kind = kind
        self.path = path
        self.line = line
        self.col = col
        self.aliases = aliases or (name,)


def _env_usages(
    project: Project, mod: Module,
    consts: Dict[str, Dict[str, str]],
) -> Iterator[_Usage]:
    path = str(mod.path)

    def env_name(node: ast.AST) -> Optional[str]:
        name = _str_const(node)
        if name is None and isinstance(node, ast.Name):
            # `os.environ.get(BREAKER_COOLDOWN_ENV)` — the name lives
            # in a module constant, possibly imported.
            dotted = project.resolve_dotted(node, mod.imports)
            if dotted is not None and "." in dotted:
                owner, _, const = dotted.rpartition(".")
                name = consts.get(owner, {}).get(const)
            if name is None:
                name = consts.get(mod.name, {}).get(node.id)
        if name is not None and _ENV_NAME_RE.match(name):
            return name
        return None

    for node in ast.walk(mod.tree):
        name: Optional[str] = None
        if isinstance(node, ast.Call):
            dotted = _receiver_text(node.func)
            if dotted.endswith(_ENV_CALLS) and node.args:
                name = env_name(node.args[0])
            elif (
                "env" in dotted.rpartition(".")[2].lower() and node.args
            ):
                # repo-local helpers: `_env_int("FISHNET_X")` etc.
                name = env_name(node.args[0])
        elif isinstance(node, ast.Subscript):
            if _receiver_text(node.value).endswith("environ"):
                name = env_name(node.slice)
        elif isinstance(node, ast.Compare):
            if (
                len(node.ops) == 1
                and isinstance(node.ops[0], (ast.In, ast.NotIn))
                and _receiver_text(node.comparators[0]).endswith("environ")
            ):
                name = env_name(node.left)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            # `SNAPSHOT_ENV = "FISHNET_EVAL_CACHE_SNAPSHOT"` — naming a
            # knob for other modules to read through IS a usage.
            target = node.targets[0]
            if isinstance(target, ast.Name) and target.id.endswith("ENV"):
                name = _str_const(node.value)
                if name is not None and not _ENV_NAME_RE.match(name):
                    name = None
        if name is not None:
            yield _Usage(name, "env", path, node.lineno, node.col_offset)


def _cli_ini_usages(project: Project, mod: Module) -> Iterator[_Usage]:
    in_scope = mod.name in _CLI_SCOPE or not mod.name.startswith(
        "fishnet_tpu."
    )
    if not in_scope:
        return
    path = str(mod.path)
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ):
            if node.func.attr == "add_argument":
                longs = tuple(
                    s for s in (_str_const(a) for a in node.args)
                    if s is not None and s.startswith("--")
                )
                for opt in longs:
                    yield _Usage(
                        opt, "cli", path, node.lineno, node.col_offset,
                        aliases=longs,
                    )
            elif node.func.attr in ("get", "has_option") and len(
                node.args
            ) >= 2:
                section = node.args[0]
                if (
                    isinstance(section, ast.Name)
                    and "SECTION" in section.id.upper()
                ) or _str_const(section) is not None:
                    key = _str_const(node.args[1])
                    if key is not None and _INI_KEY_RE.match(key):
                        yield _Usage(
                            key, "ini", path, node.lineno, node.col_offset
                        )
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
            if (
                isinstance(target, ast.Name)
                and "INI_FIELDS" in target.id
                and isinstance(node.value, (ast.Tuple, ast.List))
            ):
                for elt in node.value.elts:
                    if isinstance(elt, (ast.Tuple, ast.List)) and elt.elts:
                        key = _str_const(elt.elts[0])
                        if key is not None and _INI_KEY_RE.match(key):
                            yield _Usage(
                                key, "ini", path, elt.lineno,
                                elt.col_offset,
                            )


class EscapeHatchRule:
    """R8 — every env/CLI/ini knob declared in analysis/registry.py,
    every declared knob still used, every doc/test pointer valid."""

    id = "R8"
    name = "escape-hatch-registry"

    def __init__(self, knobs=None):
        if knobs is None:
            # The one sanctioned import of "analyzed" code: the
            # analyzer's OWN contract data (plain tuples, no runtime).
            from fishnet_tpu.analysis import registry as _registry
            knobs = _registry.KNOBS
            self._registry_path: Optional[Path] = Path(_registry.__file__)
        else:
            self._registry_path = None
        self._knobs = tuple(knobs)

    def check(self, project: Project) -> Iterator[Finding]:
        declared = {(k.kind, k.name): k for k in self._knobs}
        consts = _module_str_constants(project)
        usages: List[_Usage] = []
        for mod in project.modules.values():
            if mod.name.startswith("fishnet_tpu.analysis"):
                continue  # the contract itself + fixtures
            usages.extend(_env_usages(project, mod, consts))
            usages.extend(_cli_ini_usages(project, mod))
        out: List[Finding] = []
        seen: Set[Tuple[str, str]] = set()
        reported: Set[Tuple[str, str]] = set()
        for u in usages:
            covered = any(
                (u.kind, alias) in declared for alias in u.aliases
            )
            for alias in u.aliases:
                seen.add((u.kind, alias))
            if covered or (u.kind, u.name) in reported:
                continue
            reported.add((u.kind, u.name))
            out.append(Finding(
                rule=self.id, path=u.path, line=u.line, col=u.col,
                message=(
                    f"{u.kind} knob `{u.name}` is read here but not "
                    "declared in fishnet_tpu/analysis/registry.py — "
                    "undeclared escape hatches drift from docs and "
                    "tests until nobody knows what they do"
                ),
                suggestion=(
                    "add a Knob(name, kind, default, documented_in, "
                    "tested_by) row to analysis/registry.py (and a doc "
                    "line while you still remember the semantics)"
                ),
            ))
        # Reverse direction + pointer validation: only meaningful
        # against the real package (fixture projects see a slice).
        full_run = any(
            m.name.startswith("fishnet_tpu.") and "analysis" not in m.name
            for m in project.modules.values()
        )
        if full_run and self._registry_path is not None:
            reg_path = str(self._registry_path)
            reg_lines = self._registry_path.read_text(
                encoding="utf-8"
            ).splitlines()
            repo_root = self._registry_path.resolve().parents[2]

            def row_line(name: str) -> int:
                needle = f'"{name}"'
                for i, text in enumerate(reg_lines, start=1):
                    if needle in text:
                        return i
                return 1

            # Top-level scripts (chip_smoke.py, __graft_entry__.py,
            # tools/*.py) read knobs too but sit outside the analyzed
            # package — a cheap text probe keeps their knobs from
            # reading as dead.
            script_text = "".join(
                p.read_text(encoding="utf-8", errors="replace")
                for pattern in ("*.py", "tools/*.py")
                for p in sorted(repo_root.glob(pattern))
            )
            for (kind, name), knob in sorted(declared.items()):
                if (kind, name) not in seen and name not in script_text:
                    out.append(Finding(
                        rule=self.id, path=reg_path, line=row_line(name),
                        col=0,
                        message=(
                            f"declared {kind} knob `{name}` has no "
                            "usage left in the tree — the registry row "
                            "describes a dead switch"
                        ),
                        suggestion="delete the row (or restore the knob)",
                    ))
                    continue
                probe = name.lstrip("-")
                for label, rel in (
                    ("documented_in", knob.documented_in),
                    ("tested_by", knob.tested_by),
                ):
                    if rel is None:
                        continue
                    target = repo_root / rel
                    if not target.exists():
                        out.append(Finding(
                            rule=self.id, path=reg_path,
                            line=row_line(name), col=0,
                            message=(
                                f"`{name}`: {label} points at `{rel}`, "
                                "which does not exist"
                            ),
                            suggestion="fix the pointer",
                        ))
                    elif probe not in target.read_text(
                        encoding="utf-8", errors="replace"
                    ):
                        out.append(Finding(
                            rule=self.id, path=reg_path,
                            line=row_line(name), col=0,
                            message=(
                                f"`{name}`: {label} points at `{rel}`, "
                                f"but that file never mentions "
                                f"`{probe}` — the pointer has rotted"
                            ),
                            suggestion=(
                                "re-point it at a file that actually "
                                "covers the knob"
                            ),
                        ))
        yield from sorted(out, key=lambda f: (f.path, f.line, f.col))
