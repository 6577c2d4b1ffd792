"""The records stay true to the tree: a document that names a file names one
that exists, and every key of ``engine.stats`` has a reader outside the engine
(PERF.md section 3's audit, as a test: a key only a retired tool reads fails
here on arrival)."""
import glob
import os
import re

import pytest

from paddle_tpu.inference.llm_engine import default_engine_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: history files (CHANGES.md, ROADMAP.md, PERF.md) name deleted files on purpose
DOCUMENTS = ["README.md", "docs/architecture.md", "docs/distributed.md",
             "docs/migration.md", ".claude/skills/verify/SKILL.md"]

_CITED = re.compile(r"`([^`\s]*/[^`\s]*\.[A-Za-z0-9]+)(?::[0-9][^`]*)?`")


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_cites_files_that_exist(doc):
    """Every back-ticked path with a ``/`` and a file extension exists, from
    the repo root or from ``paddle_tpu/`` (a trailing ``:line`` stripped;
    globs, ``<placeholders>``, brace sets and absolute paths skipped)."""
    with open(os.path.join(REPO, doc)) as f:
        cited = set(_CITED.findall(f.read()))
    missing = sorted(
        path for path in cited
        if not re.search(r"[*<>{}|]", path) and not path.startswith("/")
        and not any(os.path.exists(os.path.join(REPO, base, path))
                    for base in ("", "paddle_tpu")))
    assert not missing, f"{doc} cites files that do not exist: {missing}"


#: where a reader of ``engine.stats`` may live
READERS = ["paddle_tpu/serving", "paddle_tpu/profiler", "benchmark/metrics",
           "benchmark/harness", "tests"]


def test_engine_stats_keys_each_have_a_reader():
    me = os.path.abspath(__file__)
    paths = [p for top in READERS for p in glob.glob(
        os.path.join(REPO, top, "**", "*.py"), recursive=True) if p != me]
    text = "\n".join(open(p).read() for p in paths)
    unread = sorted(k for k in default_engine_stats()
                    if not re.search(rf"\b{k}\b", text))
    assert not unread, f"engine.stats keys nobody reads: {unread}"


def test_engine_names_no_state_kind_or_model_counter():
    """``llm_engine.py`` asks ``cache_layout.Layout`` and books what the
    model declares: it compares no kind's name, names no counter of a
    model's layers and keeps no flag derived from a layout."""
    with open(os.path.join(REPO, "paddle_tpu/inference/llm_engine.py")) as f:
        source = f.read()
    for what, pattern in (
            ("a state kind's name",
             r'"paged_kv(_looped)?"|"paged_latent"|"recurrent"'),
            ("a model's counter", r"\b(moe_|ret_state_|ret_rows_)\w*"),
            ("a flag of its own over the layout",
             r"_kv_only|_has_paged|_has_recurrent|_kv_kind")):
        found = sorted({m.group(0) for m in re.finditer(pattern, source)})
        assert not found, f"llm_engine.py names {what}: {found}"
    from paddle_tpu.inference import LLMEngine
    assert isinstance(LLMEngine._loop_steps, property)
