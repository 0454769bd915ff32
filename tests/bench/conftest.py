import os
import sys

import pytest

# the benchmark package lives at the repository root (bench/)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
# cells.py holds checks that several test files apply to a cell
pytest.register_assert_rewrite("cells")
