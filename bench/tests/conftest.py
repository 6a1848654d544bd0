"""The benchmark's own tests import it as the ``bench`` package and the
program from ``src``."""
import pathlib
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
for p in (CHECKOUT, CHECKOUT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
