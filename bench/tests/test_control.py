"""The control (the reference with its non-key values rounded through
bfloat16) has to be refused by the comparison in every cell."""
import pytest

import control
import run
from conftest import CELLS, tiny


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
@pytest.mark.parametrize("name", CELLS)
def test_control_is_refused(name, seed):
    found = control.readings(tiny(name, 1000), seed)
    assert any(found[k] > run.LIMITS[k] for k in run.LIMITS), found
