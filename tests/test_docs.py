"""The documents name files that exist.

A document that shows ``python <path>`` or names a ``.py``, ``.json`` or
``.md`` file in backticks is a promise that the file is there; a deletion
that leaves the promise behind fails here, and the cure is to repair the
document."""

import os
import re

import pytest

pytestmark = pytest.mark.fast

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md", "docs/KERNELS.md", "docs/OBSERVABILITY.md",
    "docs/SCALING.md", "docs/SERVING.md", "docs/PARITY_CURVE.md",
    "docs/demo_run/README.md", "PARITY.md", "BASELINE.md",
    "benchmark/README.md", ".claude/skills/verify/SKILL.md",
]

# the source repository's own files, which the documents cite as the
# reference's, not as this checkout's
REFERENCE_FILES = {"model.py", "dataloader.py"}

_COMMAND = re.compile(r"\bpython3?\s+(?:-\w+\s+)*([\w./-]+\.py)\b")
_BACKTICKED = re.compile(r"`([^`\s]+)`")
_SUFFIXES = (".py", ".json", ".md")


@pytest.fixture(scope="module")
def checkout_files() -> set[str]:
    """Every file of the checkout, relative to its root; what a run
    leaves behind (caches, logs, chip output) is not the repository."""
    skip = {".git", ".cache", "chiprun_out", "__pycache__", ".pytest_cache",
            "log", "edu_fineweb10B", "hellaswag"}
    found = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        for name in files:
            found.add(os.path.relpath(os.path.join(root, name), REPO))
    return found


def _named_paths(text: str) -> set[str]:
    named = set(_COMMAND.findall(text))
    for token in _BACKTICKED.findall(text):
        # `scripts/obs_report.py:120`, `benchmark/run.py,` and the like
        token = token.rstrip(".,;:)").split(":")[0]
        if token.endswith(_SUFFIXES) and re.fullmatch(r"[\w./-]+", token):
            named.add(token)
    return named


@pytest.mark.parametrize("document", DOCUMENTS)
def test_paths_named_in_the_document_exist(document, checkout_files):
    files = checkout_files
    basenames = {os.path.basename(f) for f in files}
    with open(os.path.join(REPO, document)) as f:
        named = _named_paths(f.read())
    assert named, f"{document} names no file: the scan has gone blind"
    dangling = sorted(
        path for path in named - REFERENCE_FILES
        if not any(os.path.normpath(os.path.join(base, path)) in files
                   for base in ("", "mamba_distributed_tpu",
                                os.path.dirname(document)))
        and not ("/" not in path and path in basenames)
    )
    assert not dangling, (
        f"{document} names files that do not exist: {dangling}; "
        f"repair the document")
