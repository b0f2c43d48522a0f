"""Every file a document names in backticks exists.

One case per document: ``README.md``, ``docs/*.md`` and
``examples/README.md``. ``docs/perf_experiments.md`` is left out: it is
the archive of the rounds before the ledger and names the tools of those
rounds, which left the tree with the code of its rejected experiments.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCHIVE = "docs/perf_experiments.md"
ROOTS = ("tools/", "docs/", "horovod_tpu/", "benchmark/", "examples/",
         "tests/")
SUFFIXES = (".py", ".md", ".json")
PLACEHOLDERS = "<*{$"


def _documents():
    docs = sorted(os.path.relpath(p, REPO)
                  for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
    return ["README.md", *[d for d in docs if d != ARCHIVE],
            "examples/README.md"]


def _named_files(text):
    """Backticked tokens that name a file of the tree by its path from
    the root, a ``:line`` suffix stripped."""
    for token in re.findall(r"`([^`\n]+)`", text):
        path = re.sub(r":\d+(-\d+)?$", "", token.strip())
        if (path.startswith(ROOTS) and path.endswith(SUFFIXES)
                and not any(c in path for c in PLACEHOLDERS)):
            yield path


@pytest.mark.parametrize("document", _documents())
def test_document_names_only_files_of_the_tree(document):
    with open(os.path.join(REPO, document)) as f:
        named = set(_named_files(f.read()))
    dangling = sorted(p for p in named
                      if not os.path.isfile(os.path.join(REPO, p)))
    assert not dangling, f"{document} names files that do not exist"
