"""The tier x operator matrix (``tests/tier_matrix.py``) with the
store built and served under the ``jit`` engine setting."""

import pytest
import tier_matrix as tm

SETTING = "jit"


@pytest.fixture(scope="module", params=tm.RELATIONS)
def served(request, tmp_path_factory):
    return tm.serve(request.param, SETTING, str(tmp_path_factory.mktemp(request.param)))


@pytest.mark.parametrize("case", tm.CASES, ids=tm.CASE_IDS)
def test_operator(served, case):
    tm.run_case(served, case)
