import os
import sys

# Tests run on the single real CPU device (the 512-device override is
# dry-run-only, per the assignment).  Make repro importable when pytest is
# invoked without PYTHONPATH=src.
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, os.path.abspath(_SRC))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
