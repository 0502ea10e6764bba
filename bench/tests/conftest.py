"""The benchmark's tests import it as the package ``bench`` and the program
from ``src``, whatever directory pytest starts in."""
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
