"""The two manifest roots tests/benchmark holds its contracts on."""

import pytest

from benchmark.manifest import Manifest

from tests.benchmark.fixture import make_root


@pytest.fixture(scope='session')
def shipped():
    """The checkout's own manifest."""
    return Manifest()


@pytest.fixture(scope='session')
def fifth(tmp_path_factory):
    """``fixture/make_root``'s root: the checkout's entries and files, and a
    fifth cell of a seeded configuration appended the way a later PR would."""
    return Manifest(make_root.build(
        str(tmp_path_factory.mktemp('fifth_cell') / 'root')))
