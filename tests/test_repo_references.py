"""The documents and the CI workflow name only files that are in the tree.

One case a file. A path counts as named when it is written as
``scripts/*.py``, ``tests/**/*.py``, ``handyrl_tpu/**/*.py``,
``docs/*.md``, anything under ``benchmark/``, or a bare ``*.py`` /
``*.json`` / ``*.jsonl`` file name (``train.py`` for
``handyrl_tpu/train.py``: a bare name has to be some file's name). What a
document names on purpose although the tree does not hold it is listed in
``NAMED_BUT_ABSENT`` with the reason.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (['README.md', 'CONTRIBUTING.md', 'PARITY.md']
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, 'docs', '*.md')))
             + ['.github/workflows/test.yaml'])

# a path from the root; the look-behind keeps `x/scripts/y.py` and
# `serve://...` out, a glob or a `<placeholder>` ends the match
_PATHED = re.compile(
    r'(?<![\w/.<>*-])('
    r'scripts/[\w-]+\.py|tests/[\w/-]+\.py|handyrl_tpu/[\w/-]+\.py'
    r'|docs/[\w-]+\.md|benchmark/[\w./-]*)')
_BARE = re.compile(r'(?<![\w/.<>*-])([A-Za-z_][\w.-]*\.(?:py|jsonl|json))\b')

# files a run writes, which a document may name and the tree never holds
WRITTEN_AT_RUN_TIME = {'metrics.jsonl', 'out.json', 'registry.json',
                       'verdict.json'}

NAMED_BUT_ABSENT = {
    'PARITY.md': {
        # the "Reference | Here" table names the reference's own script
        'scripts/make_onnx_model.py',
    },
}


def _basenames():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith('.')
                   and d not in ('chiprun_out', '__pycache__', 'models')]
        names.update(files)
    return names


@pytest.mark.parametrize('document', DOCUMENTS)
def test_document_names_only_files_in_the_tree(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    allowed = NAMED_BUT_ABSENT.get(document, set())
    known = _basenames() | WRITTEN_AT_RUN_TIME
    missing = []
    for match in _PATHED.finditer(text):
        path = match.group(1).rstrip('.,;:')
        if path not in allowed and \
                not os.path.exists(os.path.join(REPO, path)):
            missing.append(path)
    for match in _BARE.finditer(text):
        if match.group(1) not in known and match.group(1) not in allowed:
            missing.append(match.group(1))
    assert not missing, '%s names what the tree does not hold: %s' % (
        document, sorted(set(missing)))
    # an allowance nothing uses any more is itself dangling
    assert all(name in text for name in allowed), (document, allowed)
