"""docs/api/ stays in sync with the code: the generator's output for a
couple of load-bearing modules must match the committed pages, and every
committed page must correspond to an importable module (no orphans)."""

import os

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
API = os.path.join(REPO, "docs", "api")


def test_api_pages_exist_and_cover_core_modules():
    assert os.path.isdir(API), "run tools/make_api_docs.py"
    pages = {f for f in os.listdir(API) if f.endswith(".md")}
    for must in (
        "index.md",
        "analytics_zoo_tpu_common_engine.md",
        "analytics_zoo_tpu_parallel_pipeline.md",
        "analytics_zoo_tpu_parallel_strategies.md",
        "analytics_zoo_tpu_pipeline_estimator_estimator.md",
        "analytics_zoo_tpu_ops_moe.md",
        "analytics_zoo_tpu_ops_pallas_flash_attention.md",
    ):
        assert must in pages, must
    assert len(pages) > 80  # the full per-module sweep, not a stub


def test_no_orphan_pages():
    """Every committed page corresponds to an importable module — a
    rename without regeneration leaves a stale page behind."""
    import importlib

    for f in os.listdir(API):
        if not f.endswith(".md") or f == "index.md":
            continue
        modname = f[:-3].replace("analytics_zoo_tpu_", "", 1)
        # module paths may contain underscores themselves: try the
        # greedy candidates ("a_b_c" -> a.b.c, a.b_c, a_b.c, ...)
        parts = modname.split("_")
        ok = False
        for mask in range(1 << max(0, len(parts) - 1)):
            cand, seg = [], parts[0]
            for i, p in enumerate(parts[1:]):
                if mask >> i & 1:
                    seg += "_" + p
                else:
                    cand.append(seg)
                    seg = p
            cand.append(seg)
            try:
                importlib.import_module(
                    "analytics_zoo_tpu." + ".".join(cand))
                ok = True
                break
            except ImportError:
                continue
        assert ok, f"orphan page {f}: no importable module matches"


def test_index_links_every_page():
    """The TOC and the page set move together: every committed page is
    linked from index.md and every link resolves."""
    import re

    with open(os.path.join(API, "index.md")) as f:
        idx = f.read()
    links = set(re.findall(r"\]\((\S+\.md)\)", idx))
    pages = {f for f in os.listdir(API)
             if f.endswith(".md") and f != "index.md"}
    assert links == pages, (links ^ pages)


def test_committed_pages_match_generator():
    """Regenerate EVERY page in memory and compare against the committed
    tree — drift anywhere means someone changed an API without rerunning
    tools/make_api_docs.py."""
    from tools.make_api_docs import generate

    pages, _ = generate()
    assert len(pages) > 80
    stale = []
    for modname, want in pages.items():
        path = os.path.join(API, modname.replace(".", "_") + ".md")
        if not os.path.exists(path):
            stale.append(modname + " (missing)")
            continue
        with open(path) as f:
            if f.read() != want:
                stale.append(modname)
    assert not stale, (
        f"stale pages {stale[:5]} — rerun tools/make_api_docs.py")


def test_cited_records_and_tools_exist():
    """A root-level record (``NAME_r<n>.json``), the root's old harness
    script (second pattern) or a ``tools/*.py`` that the README, a guide
    under docs/ or the package's sources name is a file of the tree: a
    deleted record cannot be cited as what holds a number."""
    import glob
    import re

    sources = [os.path.join(REPO, "README.md")]
    sources += glob.glob(os.path.join(REPO, "docs", "*.md"))
    sources += glob.glob(os.path.join(REPO, "analytics_zoo_tpu", "**",
                                      "*.py"), recursive=True)
    cited = {
        r"\b[A-Z][A-Z_]*_r\d+\.json\b": REPO,
        r"(?<![\w/])bench\.py\b": REPO,
        r"\btools/(\w+\.py)\b": os.path.join(REPO, "tools"),
    }
    dangling = set()
    for path in sources:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for pattern, where in cited.items():
            for m in re.finditer(pattern, text):
                name = m.group(m.lastindex or 0)
                if not os.path.exists(os.path.join(where, name)):
                    dangling.add((os.path.relpath(path, REPO), name))
    assert not dangling, sorted(dangling)
