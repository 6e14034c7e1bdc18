"""Docs consistency: DESIGN.md exists and every §-reference resolves.

The same check runs as a blocking CI step (tools/check_design_refs.py);
having it in the tier-1 suite catches dangling references locally before a
push.  Also covers the user guides under docs/: every python fence must
parse and every backticked repo path must exist, so guide snippets cannot
silently rot.
"""
import ast
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_design_md_references_resolve():
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_design_refs.py"),
         ROOT],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr


def test_design_md_has_cited_sections():
    """The sections the codebase has cited since before DESIGN.md existed."""
    with open(os.path.join(ROOT, "DESIGN.md")) as f:
        text = f.read()
    for sec in ("## §2", "## §4", "## §5", "## §8", "## §9"):
        assert sec in text, f"DESIGN.md lost its {sec} section"
    # octree.py cites "§2, assumption 3" — keep the numbered log intact
    assert "3. **Expansions are formed about static geometric box centers" \
        in text
    # PR 7: the probe subsystem's contract section
    assert "## §12" in text, "DESIGN.md lost its §12 (probe subsystem)"


def test_probes_guide_exists_and_is_linked():
    path = os.path.join(ROOT, "docs", "probes.md")
    assert os.path.isfile(path), "docs/probes.md missing"
    with open(os.path.join(ROOT, "README.md")) as f:
        assert "docs/probes.md" in f.read(), \
            "README.md no longer links the probes guide"


def test_probes_guide_python_snippets_parse():
    """Every ```python fence in docs/probes.md must be valid syntax."""
    with open(os.path.join(ROOT, "docs", "probes.md")) as f:
        text = f.read()
    fences = re.findall(r"```python\n(.*?)```", text, re.S)
    assert len(fences) >= 3, "the guide lost its worked examples"
    for i, snippet in enumerate(fences):
        try:
            ast.parse(snippet)
        except SyntaxError as e:
            raise AssertionError(
                f"docs/probes.md python fence #{i} does not parse: {e}\n"
                f"{snippet}") from None


def test_probes_guide_referenced_paths_exist():
    """Backticked repo-relative paths in the guide must exist on disk."""
    with open(os.path.join(ROOT, "docs", "probes.md")) as f:
        text = f.read()
    paths = re.findall(
        r"`((?:src|tests|examples|benchmarks|docs|tools)/[\w./]+?"
        r"\.(?:py|md))(?:::\w+)?`", text)
    assert "examples/lesion.py" in paths        # the walkthroughs' anchors
    assert "examples/topographic_map.py" in paths
    for p in sorted(set(paths)):
        assert os.path.isfile(os.path.join(ROOT, p)), \
            f"docs/probes.md references {p}, which does not exist"


def test_serve_guide_exists_and_is_linked():
    path = os.path.join(ROOT, "docs", "serve.md")
    assert os.path.isfile(path), "docs/serve.md missing"
    with open(os.path.join(ROOT, "README.md")) as f:
        assert "docs/serve.md" in f.read(), \
            "README.md no longer links the serving guide"
    with open(os.path.join(ROOT, "DESIGN.md")) as f:
        assert "## §14" in f.read(), \
            "DESIGN.md lost its §14 (serving / padded subdomains)"


def test_serve_guide_python_snippets_parse():
    """Every ```python fence in docs/serve.md must be valid syntax."""
    with open(os.path.join(ROOT, "docs", "serve.md")) as f:
        text = f.read()
    fences = re.findall(r"```python\n(.*?)```", text, re.S)
    assert len(fences) >= 3, "the guide lost its worked examples"
    for i, snippet in enumerate(fences):
        try:
            ast.parse(snippet)
        except SyntaxError as e:
            raise AssertionError(
                f"docs/serve.md python fence #{i} does not parse: {e}\n"
                f"{snippet}") from None


def test_serve_guide_referenced_paths_exist():
    """Backticked repo-relative paths in the guide must exist on disk."""
    with open(os.path.join(ROOT, "docs", "serve.md")) as f:
        text = f.read()
    paths = re.findall(
        r"`((?:src|tests|examples|benchmarks|docs|tools)/[\w./]+?"
        r"\.(?:py|md))(?:::\w+)?`", text)
    assert "examples/serve_demo.py" in paths
    assert "tests/test_serve_integration.py" in paths
    for p in sorted(set(paths)):
        assert os.path.isfile(os.path.join(ROOT, p)), \
            f"docs/serve.md references {p}, which does not exist"


def test_audit_guide_exists_and_is_linked():
    path = os.path.join(ROOT, "docs", "audit.md")
    assert os.path.isfile(path), "docs/audit.md missing"
    with open(os.path.join(ROOT, "README.md")) as f:
        assert "docs/audit.md" in f.read(), \
            "README.md no longer links the auditor guide"
    with open(os.path.join(ROOT, "DESIGN.md")) as f:
        assert "## §15" in f.read(), \
            "DESIGN.md lost its §15 (contract auditor)"


def test_audit_guide_python_snippets_parse():
    """Every ```python fence in docs/audit.md must be valid syntax."""
    with open(os.path.join(ROOT, "docs", "audit.md")) as f:
        text = f.read()
    fences = re.findall(r"```python\n(.*?)```", text, re.S)
    assert len(fences) >= 3, "the guide lost its worked examples"
    for i, snippet in enumerate(fences):
        try:
            ast.parse(snippet)
        except SyntaxError as e:
            raise AssertionError(
                f"docs/audit.md python fence #{i} does not parse: {e}\n"
                f"{snippet}") from None


def test_audit_guide_referenced_paths_exist():
    """Backticked repo-relative paths in the guide must exist on disk."""
    with open(os.path.join(ROOT, "docs", "audit.md")) as f:
        text = f.read()
    paths = re.findall(
        r"`((?:src|tests|examples|benchmarks|docs|tools)/[\w./]+?"
        r"\.(?:py|md))(?:::\w+)?`", text)
    assert "tools/run_audit.py" in paths
    assert "tests/test_vmap_deletion.py" in paths
    for p in sorted(set(paths)):
        assert os.path.isfile(os.path.join(ROOT, p)), \
            f"docs/audit.md references {p}, which does not exist"


def test_tracing_guide_exists_and_is_linked():
    path = os.path.join(ROOT, "docs", "tracing.md")
    assert os.path.isfile(path), "docs/tracing.md missing"
    with open(os.path.join(ROOT, "README.md")) as f:
        assert "docs/tracing.md" in f.read(), \
            "README.md no longer links the tracing guide"


def test_tracing_guide_python_snippets_parse():
    """Every ```python fence in docs/tracing.md must be valid syntax."""
    with open(os.path.join(ROOT, "docs", "tracing.md")) as f:
        text = f.read()
    fences = re.findall(r"```python\n(.*?)```", text, re.S)
    assert len(fences) >= 3, "the guide lost its worked examples"
    for i, snippet in enumerate(fences):
        try:
            ast.parse(snippet)
        except SyntaxError as e:
            raise AssertionError(
                f"docs/tracing.md python fence #{i} does not parse: {e}\n"
                f"{snippet}") from None


def test_tracing_guide_names_every_scope_and_span():
    """The guide lists each scope the engine opens and each span the
    recorder keeps, and its backticked repo paths exist."""
    def source(module):
        with open(os.path.join(ROOT, "src", "repro", "core", module)) as f:
            return f.read()

    with open(os.path.join(ROOT, "docs", "tracing.md")) as f:
        text = f.read()
    core = "".join(map(source, ("engine.py", "distributed.py", "traversal.py")))
    names = set(re.findall(r'named_scope\(f?"([\w/{}]+)"\)', core))
    dist = source("distributed.py")
    names |= set(re.findall(r'_exchange\(\s*"(\w+)"', dist))
    names |= set(re.findall(r'_gatherer\("(\w+)"', dist))
    names.discard("exchange/{what}")
    assert {"update/find", "descent", "spikes"} <= names
    for name in sorted(names):
        assert name in text, f"docs/tracing.md does not name {name}"
    for name in ("engine.init", "compile.trace", "compile.lower",
                 "compile.backend", "compile.cache_hit", "compile.cache_miss"):
        assert f"`{name}`" in text
    paths = re.findall(
        r"`((?:src|tests|examples|benchmarks|docs|tools)/[\w./]+?"
        r"\.(?:py|md))(?:::\w+)?`", text)
    assert "src/repro/runtime/spans.py" in paths
    for p in sorted(set(paths)):
        assert os.path.isfile(os.path.join(ROOT, p)), \
            f"docs/tracing.md references {p}, which does not exist"
