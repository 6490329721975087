"""The benchmark's CPU tests: ``JAX_PLATFORMS=cpu python -m pytest
bench/tests`` from the root of the checkout.  The program is imported from
``src/`` as the harness imports it."""
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[2] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))
