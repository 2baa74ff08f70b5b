"""The documents name only what the tree has: a file a document
back-ticks exists, and a flag README passes to a CLI is that CLI's."""

import os
import re

import pytest

from test_cli_surface import CLIS, FLAG, flags_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "OBSERVABILITY.md", "DISTRIBUTED.md", "PARITY.md"]
OURS = ("eventgpt_tpu/", "scripts/", "script/", "tests/", "benchmark/",
        "native/")
BARE = re.compile(r"[A-Za-z_][\w.\-]*\.(md|json|py)")
# Bare names that are not this tree's: a checkpoint's, a run's output
# directory's, the reference's.
NOT_OURS = {"config.json", "heartbeat.json", "inference.py"}


def _basenames() -> set:
    names = set()
    for top, dirs, files in os.walk(ROOT):
        # Not what git would commit: caches, an unpacked parent, chip output.
        dirs[:] = [d for d in dirs if d[0] not in "._" and d != "chiprun_out"]
        names.update(files)
    return names


def _spans(text: str):
    """Back-ticked spans, but for table cells under a header cell named
    ``Reference``: PARITY.md's first column holds the reference's paths."""
    skip = None
    for line in text.splitlines():
        cells = line.split("|") if line.lstrip().startswith("|") else None
        if cells is None:
            skip = None
        elif skip is None:
            heads = [c.strip() for c in cells]
            skip = heads.index("Reference") if "Reference" in heads else -1
        parts = (line,) if cells is None else (
            c for i, c in enumerate(cells) if i != skip)
        for part in parts:
            yield from re.findall(r"`([^`]+)`", part)


def _exists(word: str) -> bool:
    if os.path.exists(os.path.join(ROOT, word)):
        return True
    # ``eventgpt_tpu/utils/compile_cache.enable_compile_cache``
    module = word.rsplit(".", 1)[0] + ".py"
    return os.path.exists(os.path.join(ROOT, module))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    basenames, missing, seen = _basenames(), set(), 0
    for span in _spans(text):
        for word in span.split():
            word = word.strip(".,;()[]\"'").split("::")[0]
            word = re.sub(r":[\d,\-]+$", "", word)       # path:line
            if re.search(r"[<>*{}$]", word):              # a pattern
                continue
            if word.startswith(OURS):
                seen += 1
                if not _exists(word):
                    missing.add(word)
            elif BARE.fullmatch(word) and word not in NOT_OURS:
                seen += 1
                if not (os.path.exists(os.path.join(ROOT, word))
                        or word in basenames):
                    missing.add(word)
    assert seen and not missing, sorted(missing)


def test_readme_flags_are_flags():
    """Inside README's fenced blocks, a ``--flag`` on a line that runs
    ``eventgpt_tpu.cli.<name>`` is that parser's; one in a comment is
    some CLI's."""
    with open(os.path.join(ROOT, "README.md")) as f:
        text = f.read()
    any_cli = set().union(*(flags_of(c) for c in CLIS))
    unknown, checked = [], 0
    for block in re.findall(r"```[a-z]*\n(.*?)```", text, re.S):
        for line in block.replace("\\\n", " ").splitlines():
            flags = set(FLAG.findall(line))
            ran = re.search(r"eventgpt_tpu\.cli\.(\w+)", line)
            if ran:
                known = flags_of(ran.group(1))
            elif line.lstrip().startswith("#"):
                known = any_cli
            else:
                continue            # curl, a script with a parser of its own
            checked += len(flags)
            unknown += [(f, line.strip()[:60]) for f in flags - known]
    assert checked > 10 and not unknown, unknown
