"""What the documents name is there: files, and ``make`` targets.

A document that sends its reader to a file the tree no longer has, or to a
target the Makefile no longer defines, is a defect no other test sees: the
pre-chip harness was named in nine documents for five PRs after nothing read
it. One case a document; stdlib only, nothing imported from the package.

A name counts as a file when it looks like one (a known extension, or a
directory in backticks with its trailing slash) and is written relative. The
documents abbreviate (``obs/slo.py`` for ``rag_llm_k8s_tpu/obs/slo.py``,
``engine.py`` for ``rag_llm_k8s_tpu/engine/engine.py``), so a name is there
when some path of the tree ENDS with it. What is not the tree's is exempt BY
LIST below, with the reason, never by pattern.
"""

import functools
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

DOCUMENTS = sorted(
    ["README.md", "PERF.md", "Makefile", "deploy/llm/deploy.yaml"]
    + [str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md")]
)

# History is allowed to name what is gone: these are not cases at all
# (CHANGES.md, ROADMAP.md's "Recent", VERDICT.md, SURVEY.md, ADVICE.md).

#: names that look like files and are not this tree's, each with its reason
NOT_IN_TREE = {
    # the reference implementation the repo was modelled on (/root/reference)
    "rag.py": "the reference's server",
    "llm/rag.py": "the reference's server",
    "download_model.py": "the reference's downloader",
    "llm/download_model.py": "the reference's downloader",
    "ragdeploy.yaml": "the reference's manifest",
    "llm/ragdeploy.yaml": "the reference's manifest",
    "tr_technology_radar_vol_29_en.pdf": "the reference's corpus, /root/reference",
    # a published model's files, named as formats
    "config.json": "a model repository's file",
    "tokenizer.json": "a model repository's file",
    "model.safetensors.index.json": "a checkpoint's index",
    # written at run time and gitignored
    ".jax_cache/": "the compile cache, built at run time",
    "rag_executables/": "the executable store inside the compile cache directory, built at run time",
    ".bench_state/": "a benchmark run's state, built at run time",
    "chiprun_out/": "what the chip tool brings back",
    "tpu_rag_trace/": "a profiler capture's directory",
    "plugins/profile/": "a profiler capture's directory",
    "metadata.json": "a saved index's file, written at run time",
    "index.bin": "a saved index's file, written at run time",
    "embeddings.npy": "a saved index's file, written at run time",
    "perfetto_trace.json.gz": "a profiler capture's file",
    "warmth_manifest.json": "the drain's manifest, written at run time",
    "incidents/flight-20260806.json": "an example incident bundle's path",
    "BUNDLE.json": "flightview's argument, an incident bundle",
    # the driver's, outside the repository
    "TESTS_LAST_RUN.json": "the driver's record, /root/TESTS_LAST_RUN.json",
}

#: what a document may name although it is gone, because it records its going
REMOVED_NAMED_AS_HISTORY = {
    "PERF.md": {"bench.py", "scripts/bench_gate.py", "scripts/ab_fused_8b.py", "obs/regression.py"},
}

_EXT = r"(?:py|md|json|jsonl|ya?ml|toml|sh|cc|cpp|h|txt|pdf|npy|bin|gz)"
# a path: segments of word characters, dots and dashes joined by slashes, ending
# in a known extension; not preceded or followed by what would make it part of
# a URL, an absolute path, a glob or a placeholder. A directory: the same,
# alone between backticks, with its trailing slash
_PATH = re.compile(
    r"(?<![\w/.<>*$~{}:-])((?:\.?[A-Za-z_][\w.-]*/)*\.?[A-Za-z_][\w.-]*\." + _EXT + r")(?![\w/*<>{}-])"
)
_DIR = re.compile(r"`((?:\.?[A-Za-z_][\w.-]*/)+)`")
_MAKE = re.compile(r"\bmake ([a-z0-9][a-z0-9_-]*)")
_URL = re.compile(r"https?://\S+")


@functools.cache
def makefile_targets() -> set:
    text = (ROOT / "Makefile").read_text()
    return {m.group(1) for m in re.finditer(r"^([A-Za-z0-9_-]+):", text, re.M)}


def named_paths(text: str) -> set:
    text = _URL.sub(" ", text)
    names = {m.group(1) for m in _PATH.finditer(text)}
    names |= {m.group(1) for m in _DIR.finditer(text)}
    return names


@functools.cache
def tree_paths() -> list:
    """Every file and directory of the tree as ``/a/b/c`` (directories with
    a trailing slash); dot-directories and what the chip tool brings back
    are not the tree."""
    out = []
    stack = [ROOT]
    while stack:
        d = stack.pop()
        for p in d.iterdir():
            if p.name.startswith(".") and p.is_dir() or p.name in ("chiprun_out", "__pycache__"):
                continue
            rel = "/" + str(p.relative_to(ROOT))
            if p.is_dir():
                out.append(rel + "/")
                stack.append(p)
            else:
                out.append(rel)
    return out


def exists(name: str, paths: list) -> bool:
    return any(p.endswith("/" + name) for p in paths)


def english(word: str) -> bool:
    """``make sure``: a verb and its object, not a target. Outside backticks
    a word the Makefile does not define is read as a target only when it is
    spelt like one."""
    return "-" not in word and "_" not in word and not word[-1].isdigit()


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_file_and_make_target_exists(document):
    text = (ROOT / document).read_text()
    allowed = REMOVED_NAMED_AS_HISTORY.get(document, set())
    paths = tree_paths()
    missing = sorted(
        n for n in named_paths(text)
        if n not in NOT_IN_TREE and n not in allowed and not exists(n, paths)
    )
    targets = makefile_targets()
    in_backticks = set(re.findall(r"`make ([a-z0-9][a-z0-9_-]*)", text))
    no_target = sorted(
        w for w in set(_MAKE.findall(text))
        if w not in targets and (w in in_backticks or not english(w))
    )
    assert not missing and not no_target, (
        f"{document} names files the tree does not have: {missing}; "
        f"make targets the Makefile does not define: {no_target}"
    )


def test_the_documents_are_all_there():
    """The list above is built from what is on disk; the count is what the
    guard was sized for (README, PERF, Makefile, deploy.yaml, twelve docs)."""
    assert len(DOCUMENTS) >= 16
    assert all((ROOT / d).is_file() for d in DOCUMENTS)
