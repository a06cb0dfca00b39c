"""The benchmark's own tests: ``python -m pytest benchmarks/chip/tests``
from the repository root (the repository's ``pytest.ini`` collects only
``tests/``, so name this directory)."""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
