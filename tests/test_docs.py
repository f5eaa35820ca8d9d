"""Documentation contract: the public API is documented and the docs are
true. Docstring checks cover every symbol exported from ``repro.core``,
``repro.core.engine``, ``repro.core.serving``, ``repro.core.batch``,
``repro.core.runner``, ``repro.dist``, ``repro.serve`` and
``repro.pgm.datasets``; the code blocks in ``docs/engine.md``,
``docs/serving.md``, ``docs/admission.md``, ``docs/router.md`` and
``docs/workloads.md`` are executed verbatim (they are the living spec of
the engine, the serving tiers and the workload zoo); relative links
between the markdown files
must resolve, and README's doc table must link every file in ``docs/``."""

import inspect
import pathlib
import re

import pytest

DOCS = pathlib.Path(__file__).resolve().parent.parent / "docs"
REPO = DOCS.parent

PUBLIC_MODULES = ["repro.core", "repro.core.engine", "repro.core.serving",
                  "repro.core.batch", "repro.core.runner", "repro.dist",
                  "repro.serve", "repro.pgm.datasets", "repro.kernels.ops",
                  "repro.kernels.triton_update",
                  "repro.roofline.kernel_model"]


def _public_objects(modname):
    mod = pytest.importorskip(modname)
    assert hasattr(mod, "__all__"), f"{modname} must declare __all__"
    for name in mod.__all__:
        yield name, getattr(mod, name)


@pytest.mark.parametrize("modname", PUBLIC_MODULES)
def test_public_symbols_have_real_docstrings(modname):
    missing = []
    for name, obj in _public_objects(modname):
        if not (inspect.isclass(obj) or inspect.isroutine(obj)):
            continue    # constants, registries, re-exported modules
        doc = inspect.getdoc(obj) or ""
        # Reject the dataclass auto-docstring ("Name(field: type = ...)")
        # and one-word stubs: shapes/semantics need actual sentences.
        if len(doc) < 40 or doc.startswith(f"{name}("):
            missing.append(name)
    assert not missing, f"{modname}: undocumented public symbols: {missing}"


def _code_blocks(md_path):
    text = md_path.read_text()
    return re.findall(r"```python\n(.*?)```", text, flags=re.DOTALL)


@pytest.mark.parametrize("md,min_blocks", [("engine.md", 3),
                                           ("serving.md", 3),
                                           ("admission.md", 3),
                                           ("schedulers.md", 2),
                                           ("router.md", 3),
                                           ("workloads.md", 3),
                                           ("kernels.md", 3)])
def test_md_code_blocks_execute(md, min_blocks):
    blocks = _code_blocks(DOCS / md)
    assert len(blocks) >= min_blocks, f"{md} lost its executable examples"
    # Doc examples register demo schedulers/policies into the process-global
    # registries; snapshot and restore so later tests see pristine families.
    from repro.core.schedulers import SCHEDULERS
    from repro.core.serving import ADMISSION_POLICIES
    from repro.kernels.ops import UPDATE_BACKENDS
    from repro.pgm.datasets import WORKLOADS
    from repro.serve.routing import ROUTING_POLICIES
    registries = (SCHEDULERS, UPDATE_BACKENDS, ADMISSION_POLICIES,
                  ROUTING_POLICIES, WORKLOADS)
    snapshots = [dict(r) for r in registries]
    ns = {}
    try:
        for i, block in enumerate(blocks):
            try:
                exec(compile(block, f"docs/{md}[block {i}]", "exec"), ns)
            except Exception as e:     # pragma: no cover - failure reporting
                pytest.fail(f"docs/{md} block {i} failed: {e!r}\n{block}")
    finally:
        for reg, snap in zip(registries, snapshots):
            reg.clear()
            reg.update(snap)


@pytest.mark.parametrize("md", ["README.md", "docs/architecture.md",
                                "docs/schedulers.md", "docs/engine.md",
                                "docs/sharding.md", "docs/serving.md",
                                "docs/admission.md", "docs/router.md",
                                "docs/workloads.md", "docs/kernels.md"])
def test_relative_links_resolve(md):
    path = REPO / md
    broken = []
    for target in re.findall(r"\]\(([^)#]+?)(?:#[^)]*)?\)", path.read_text()):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        resolved = (path.parent / target).resolve()
        if not resolved.is_relative_to(REPO):
            continue    # GitHub-UI paths (badge/actions) live off-repo
        if not resolved.exists():
            broken.append(target)
    assert not broken, f"{md}: broken relative links: {broken}"


def test_readme_links_every_doc():
    """README's doc-links table is the docs' front door: every markdown
    file under docs/ must be linked from it (CI's docs job enforces the
    same for serving.md via grep)."""
    readme = (REPO / "README.md").read_text()
    missing = [f"docs/{p.name}" for p in sorted(DOCS.glob("*.md"))
               if f"docs/{p.name}" not in readme]
    assert not missing, f"README.md does not link: {missing}"
