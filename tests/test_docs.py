"""The documents name files that exist.

Every back-ticked token of a document that looks like a file of this
repository must be one: at the root of the checkout, or under
`polyaxon_tpu/`, where the documents' `serving/server.py` style of path
resolves. A document that sends its reader to a file that is gone (a
deleted harness, a renamed module) fails here."""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SUFFIXES = (".py", ".md", ".json", ".yaml", ".sh")


def _file_tokens(text: str) -> list[str]:
    """The words of the inline code spans that claim to be a file or a
    directory of the checkout (fenced blocks are examples, not claims).
    Words with `<`, `>`, `*` or `{`, or a leading `/` or `~`, are run-time
    paths (a run's outputs, a URL's path) and are skipped."""
    out = []
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    for span in re.findall(r"`([^`]+)`", text):
        for tok in span.split():
            tok = tok.strip("\"'(),;[]")
            if not tok or any(c in tok for c in "<>*{") or tok[0] in "/~":
                continue
            tok = tok.split("::")[0]  # `file.py::function`
            tok = re.sub(r":[\d,:-]+$", "", tok)  # `file.py:12-40`
            if tok.endswith(SUFFIXES) or tok.endswith("/"):
                out.append(tok)
    return out


@pytest.mark.parametrize(
    "doc",
    ["README.md", "docs/architecture.md", "docs/operations.md",
     "docs/polyaxonfile.md"],
)
def test_document_names_only_files_that_exist(doc):
    tokens = _file_tokens((REPO / doc).read_text())
    assert tokens, f"{doc} names no file at all: the pattern is broken"
    missing = sorted({
        t for t in tokens
        if not (REPO / t).exists() and not (REPO / "polyaxon_tpu" / t).exists()
    })
    assert not missing, f"{doc} names files that do not exist: {missing}"
