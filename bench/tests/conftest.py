"""The benchmark's own tests run on the CPU, by explicit path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

(``pyproject.toml`` points pytest at ``tests/``, so the repository's suite
does not collect them.)
"""

import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src", pathlib.Path(__file__).resolve().parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
