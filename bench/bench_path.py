"""Puts the checkout's root and ``src`` first on ``sys.path``, so the
scripts in this directory import ``bench`` and the program."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
