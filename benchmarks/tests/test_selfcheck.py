"""`python3 -m benchmarks.selfcheck`'s cases, each one test."""

import pytest

from benchmarks import selfcheck


@pytest.mark.parametrize("check", selfcheck.CHECKS, ids=lambda c: c.__name__)
def test_selfcheck(check):
    check()
