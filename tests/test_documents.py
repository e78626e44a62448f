"""The documents name files that exist.

Every back-ticked word of the documents below that ends in a source or
record extension is the path, or the end of the path, of a file of the
checkout (``ops/frontier.py``, ``basic.py`` and ``tests/test_frontier.py``
all resolve).  A deletion that leaves its file's name in a document fails
here, so the documents follow the tree."""
import fnmatch
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "PERF.md", "docs/COMPONENTS.md",
             "docs/OBSERVABILITY.md", "docs/SCOPE.md", "docs/SERVING.md",
             "docs/STREAMING.md"]
EXTENSIONS = (".py", ".md", ".json", ".jsonl", ".sh", ".ini", ".cpp")
# directories made at run time (.gitignore): never files of the checkout
_RUN_TIME_DIRS = {".git", "__pycache__", ".pytest_cache", ".jax_cache",
                  "chiprun_out", "_chip_tree"}

# names that are right and are no file of the checkout, each with its reason
NOT_IN_THE_CHECKOUT = {
    "chip_smoke.json": "chip_smoke.py writes it under --out",
    "perf_results.jsonl": "the package's event journal: .gitignore",
    "flight_*.jsonl": "the flight recorder's dump (<run_id> reads as *)",
    "lightgbm_R.cpp": "the reference's R binding, docs/SCOPE.md's ADR",
}
# the reference implementation's own files (LightGBM's src/, R-package/)
_REFERENCE = re.compile(r"^(src|include|python-package|R-package)/")


@functools.lru_cache(maxsize=None)
def _files():
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _RUN_TIME_DIRS]
        out += [os.path.relpath(os.path.join(root, f), REPO) for f in files]
    return out


def _named_files(text):
    # a fence or a doubled back-tick opens and closes a span like a single
    # one; a span may wrap
    text = text.replace("```", "`").replace("``", "`")
    assert text.count("`") % 2 == 0, "a back-tick is not closed"
    for span in re.findall(r"`([^`]+)`", text):
        for word in span.split():
            word = word.split("::")[0]
            word = re.sub(r":\d+(-\d+)?$", "", word).strip("(),;'\"")
            word = re.sub(r"<[^>]*>", "*", word)
            # an absolute path is outside the checkout by its look
            if (word.endswith(EXTENSIONS) and not word.startswith("/")
                    and not _REFERENCE.search(word)):
                yield word.lstrip("./")


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_files_that_exist(document):
    files = _files()
    with open(os.path.join(REPO, document)) as f:
        names = sorted(set(_named_files(f.read())))
    assert names, f"{document} names no file: is the pattern still right?"
    missing = [n for n in names
               if not any(fnmatch.fnmatch(n, pat) for pat in NOT_IN_THE_CHECKOUT)
               and not any(fnmatch.fnmatch(p, n) or fnmatch.fnmatch(p, "*/" + n)
                           for p in files)]
    assert not missing, (
        f"{document} names files the checkout does not hold: {missing}")
