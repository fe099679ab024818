import os

import pytest

import benchtools


@pytest.fixture
def tiny(tmp_path):
    """The benchmark's files cut to tiny sizes, in a directory of their own,
    and the spec that points there."""
    d = str(tmp_path / "bench")
    os.makedirs(d)
    return d, benchtools.tiny_bench(d)
