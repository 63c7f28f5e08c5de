"""The manifests the tests of this directory hold: BENCHMARK.json as it
is, and as the next PR would leave it (``_paths.grown``: one more
configuration, one more cell behind the newest in every list the newest
is in, one more per-layer metric that lists old cells, an old list
grown by an old cell). A test that asserts anything of the manifest
takes ``manifest`` and so runs on both: a test that holds the manifest
to a count, to a place in a list or to a closed set of lists fails on
the second before the PR that adds the next cell meets it."""

import copy

import pytest

from . import _paths


@pytest.fixture(scope="session")
def manifests(tmp_path_factory):
    """``{"root", "grown"}``: the second in a checkout of its own,
    made once a session, that holds its two new files."""
    m = _paths.manifest()
    return {"root": m, "grown": _paths.checkout_grown(
        m, tmp_path_factory.mktemp("grown") / "checkout")}


@pytest.fixture(params=["root", "grown"])
def manifest(request, manifests):
    """A copy a test may change."""
    return copy.deepcopy(manifests[request.param])
