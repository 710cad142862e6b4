"""The control: the reference in bfloat16, put in the program's place, is
refused by the same comparison, and the same pipeline in float32 is not."""

import numpy as np
import pytest

from bench import control as C
from bench.tests.conftest import SEED, tiny


@pytest.mark.parametrize("name", ["bm25-w1-steady", "hybrid768-steady"])
def test_bfloat16_control_is_refused(name):
    cell = tiny(name, n_docs=3000, rate=30.0)
    low = C.run_control(cell, SEED, 3.0)
    assert low["refused"] and low["dtype"] == "bfloat16"
    assert low["checks"]["score_rel_err"]["value"] > 10 * \
        low["checks"]["score_rel_err"]["limit"]
    f32 = C.run_control(cell, SEED, 3.0, dtype=np.float32)
    assert not f32["refused"]
