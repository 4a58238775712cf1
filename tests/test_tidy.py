"""Repo hygiene as a test (reference: src/tidy.zig runs lint as a unit
test): banned patterns, parseability, reference-citation presence."""

import ast
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = REPO / "tigerbeetle_tpu"

BANNED = [
    # Wall-clock and randomness inside the deterministic core: the simulator
    # and replicas must get time via injected providers only.
    (re.compile(r"\btime\.time\(\)"), "use the injected time provider",
     ("vsr", "testing")),
    (re.compile(r"random\.random\(\)\s*$"), "seeded PRNGs only",
     ("vsr",)),
    (re.compile(r"\bprint\("), "no prints in library code (trace/log instead)",
     ("vsr", "ops", "lsm", "oracle")),
]


def _python_files():
    return sorted(p for p in PACKAGE.rglob("*.py"))


def test_all_files_parse_and_have_docstrings():
    for path in _python_files():
        tree = ast.parse(path.read_text(), filename=str(path))
        if path.name != "__main__.py":
            assert ast.get_docstring(tree), f"{path} missing module docstring"


def test_banned_patterns():
    for path in _python_files():
        rel = path.relative_to(PACKAGE)
        text = path.read_text()
        for pattern, why, scopes in BANNED:
            if rel.parts and rel.parts[0] in scopes:
                for i, line in enumerate(text.splitlines(), 1):
                    if pattern.search(line) and "# tidy:allow" not in line:
                        raise AssertionError(f"{rel}:{i}: {why}: {line.strip()}")


def test_reference_citations_present():
    """Core modules must cite reference file:line so parity is checkable."""
    required = [
        "types.py", "state_machine.py", "multi_batch.py",
        "ops/create_kernels.py", "ops/fast_kernels.py", "ops/ledger.py",
        "vsr/replica.py", "vsr/journal.py", "vsr/superblock.py",
        "lsm/tree.py", "lsm/grid.py", "testing/cluster.py",
    ]
    for rel in required:
        text = (PACKAGE / rel).read_text()
        assert re.search(r"src/[\w/]+\.zig", text), f"{rel} lacks citations"


TRACE_CALL = re.compile(
    r"\btracer\.(?:span|count|gauge|begin|end)\(\s*(['\"]?)(Event\.(\w+))?")


def test_tracer_call_sites_use_catalog_members():
    """ISSUE 5 satellite: every tracer.span/count/gauge/begin/end call
    site references a typed catalog member (trace/event.py), never a
    string literal — the recording tracer would reject a free-form name
    at runtime, but the lint catches it before anything runs."""
    from tigerbeetle_tpu.trace import Event

    for path in _python_files():
        rel = path.relative_to(PACKAGE)
        if rel.parts and rel.parts[0] == "trace":
            continue  # the tracer's own internals
        for i, line in enumerate(path.read_text().splitlines(), 1):
            m = TRACE_CALL.search(line)
            if m is None or "# tidy:allow" in line:
                continue
            assert not m.group(1), \
                f"{rel}:{i}: tracer call with a string literal — use " \
                f"trace.Event members: {line.strip()}"
            if m.group(3):
                assert hasattr(Event, m.group(3)), \
                    f"{rel}:{i}: Event.{m.group(3)} is not in the catalog"


def test_monitoring_doc_lists_every_catalog_event():
    """docs/operating/monitoring.md is the operator rendering of the
    catalog: a new event without a documented meaning cannot ship."""
    from tigerbeetle_tpu.trace import Event

    doc = (REPO / "docs" / "operating" / "monitoring.md").read_text()
    missing = [e.name for e in Event if f"`{e.name}`" not in doc]
    assert not missing, \
        f"monitoring.md lacks catalog events: {missing}"


def test_jaxhound_pragmas_name_real_rules():
    """ISSUE 14 satellite: every `# jaxhound: allow(<rule>)` pragma in
    the tree names a rule hostdet actually enforces — a typo'd pragma
    suppresses nothing and would silently rot."""
    from tigerbeetle_tpu.jaxhound import hostdet

    for path in _python_files():
        rel = path.relative_to(PACKAGE)
        for i, line in enumerate(path.read_text().splitlines(), 1):
            m = hostdet._PRAGMA_RE.search(line)
            if m is None:
                continue
            rules = {r.strip() for r in m.group(1).split(",")
                     if r.strip()}
            unknown = rules - set(hostdet.RULES)
            assert not unknown, \
                f"{rel}:{i}: pragma names unknown jaxhound rule(s) " \
                f"{sorted(unknown)} (valid: {hostdet.RULES})"


def test_no_reference_code_imports():
    """Nothing may read from /root/reference at runtime."""
    for path in _python_files():
        assert "/root/reference" not in path.read_text(), path


# ------------------------------------------------ the tree's shape (PR 33)

def _module_name(path: pathlib.Path) -> str:
    parts = path.relative_to(REPO).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


MODULES = {_module_name(p): p for p in _python_files()}


def _imported_names(path: pathlib.Path, this: str) -> set[str]:
    """Every absolute dotted name an import statement of `path` names,
    function-level imports and the Python source held in its string
    constants (the gate's `-c` programs) included."""
    source = ast.parse(path.read_text(), filename=str(path))
    trees = [source]
    for node in ast.walk(source):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and "import" in node.value:
            try:
                trees.append(ast.parse(node.value))
            except SyntaxError:
                pass
    package = this.split(".")
    if path.name != "__init__.py":
        package = package[:-1]
    names = set()
    for tree in trees:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names.update(a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                base = package[:len(package) - node.level + 1] \
                    if node.level else []
                base = ".".join(base + ([node.module] if node.module else []))
                names.add(base)
                names.update(f"{base}.{a.name}" for a in node.names)
    return names


def _package_imports(path: pathlib.Path, this: str) -> set[str]:
    """The package's modules that `path` imports, parents included
    (importing a.b.c runs a and a.b)."""
    out = set()
    for name in _imported_names(path, this):
        while name:
            if name in MODULES:
                out.add(name)
            name = name.rpartition(".")[0]
    return out


# What `start` serves from, and what may not know what stands on it.
SERVED = ("ops", "lsm", "vsr", "oracle", "state_machine", "types")
ABOVE = {"serving", "admission", "testing", "jaxhound", "metrics", "repl",
         "cdc", "amqp", "main"}
SCRIPTS = {p.stem for p in REPO.glob("*.py")} | {
    "scripts", "perf", "chipbench", "tests"}


@pytest.mark.parametrize("pkg", SERVED)
def test_served_path_imports_nothing_above_it(pkg):
    """The fence for ROADMAP D12: the layers under `Replica` import no
    second served path, no test harness, no tool and no script."""
    checked = 0
    for name, path in MODULES.items():
        if name.split(".")[1:2] != [pkg]:
            continue
        checked += 1
        for imported in _imported_names(path, name):
            parts = imported.split(".")
            assert parts[0] not in SCRIPTS, f"{name} imports {imported}"
            assert not (parts[0] == "tigerbeetle_tpu"
                        and parts[1:2] and parts[1] in ABOVE), \
                f"{name} imports {imported}"
    assert checked, f"no module under tigerbeetle_tpu/{pkg}"


def _registered_subcommands() -> list[str]:
    tree = ast.parse((PACKAGE / "main.py").read_text())
    return [node.args[0].value for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "add_parser"]


def _documented_subcommands() -> tuple[set[str], set[str]]:
    """(README's tooling line, main.py's usage docstring)."""
    readme = re.search(r"python -m tigerbeetle_tpu \{([^}]*)\}",
                       (REPO / "README.md").read_text())
    usage = ast.get_docstring(ast.parse((PACKAGE / "main.py").read_text()))
    return (set(re.sub(r"\s", "", readme.group(1)).split(",")),
            set(re.findall(r"(?:^  |\|  )([a-z]+)\b", usage, re.M)))


REGISTERED = _registered_subcommands()


@pytest.mark.parametrize("name", REGISTERED)
def test_subcommand_is_documented(name):
    for where, documented in zip(("README.md's tooling line",
                                  "main.py's usage docstring"),
                                 _documented_subcommands()):
        assert name in documented, f"{where} lacks `{name}`"
        unknown = documented - set(REGISTERED)
        assert not unknown, f"{where} names {sorted(unknown)}, not registered"


def test_no_orphan_module():
    """Every module of the package is an entry point (`__main__`) or is
    imported, directly or through others, by an entry point, a gate leg
    or a test."""
    roots = [PACKAGE / "main.py", REPO / "chip_smoke.py",
             REPO / "scripts" / "gate.py", REPO / "__graft_entry__.py",
             REPO / "perf" / "opbudget.py",
             *sorted((REPO / "tests").glob("*.py"))]
    todo = [(path, _module_name(path)) for path in roots]
    reached = {name for _, name in todo if name in MODULES}
    while todo:
        path, name = todo.pop()
        for imported in _package_imports(path, name) - reached:
            reached.add(imported)
            todo.append((MODULES[imported], imported))
    orphans = [name for name, path in sorted(MODULES.items())
               if name not in reached
               and path.name != "__main__.py"
               and '__name__ == "__main__"' not in path.read_text()]
    assert not orphans, f"modules nothing imports: {orphans}"


# A path with a `*` in it is a pattern, not a file: the lookbehind
# keeps the tail of one (`_r*.json`) from matching.
DOC_PATH = re.compile(r"(?<![\w/*.-])[\w./-]*\w\.(?:py|json|cpp|md)\b")
DOCS = {
    "README.md": lambda: (REPO / "README.md").read_text(),
    "scripts/gate.py": lambda: ast.get_docstring(
        ast.parse((REPO / "scripts" / "gate.py").read_text())),
}


@pytest.mark.parametrize("doc", DOCS)
def test_docs_name_only_files_that_exist(doc):
    """A path a reader is sent to exists under the repo root or the
    package."""
    named = set(DOC_PATH.findall(DOCS[doc]()))
    assert len(named) >= 8, f"{doc}: the pattern found only {sorted(named)}"
    missing = [m for m in sorted(named)
               if not (REPO / m).exists() and not (PACKAGE / m).exists()]
    assert not missing, f"{doc} names files that do not exist: {missing}"
