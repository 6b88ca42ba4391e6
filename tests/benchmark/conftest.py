"""The manifest roots tests/benchmark holds its contracts and pins on.

``either_root`` is the one a configuration's own tests take their manifest
from: the checkout, and the checkout as later PRs will have left it (one
more configuration, cell, traffic file, hook, reader, metric file and two
``per_layer`` entries appended: ``fixture/make_root.grow``). A test that
reads ``raw``, ``cells``, ``metrics``, ``metrics_of`` or a loaded file runs
once on each, so a pin that indexes a list from its END passes on the first
and fails on the second, in the PR that writes it."""

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


@pytest.fixture(scope='session')
def grown(tmp_path_factory):
    """The checkout as later PRs would leave it: everything it has, and one
    of each kind of addition behind it."""
    return Manifest(make_root.grow(
        str(tmp_path_factory.mktemp('open_for_additions') / 'root')))


@pytest.fixture(scope='session', params=['checkout', 'grown'])
def either_root(request):
    """The checkout's manifest, then the grown root's."""
    return request.getfixturevalue(
        'shipped' if request.param == 'checkout' else 'grown')
