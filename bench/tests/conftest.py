"""The benchmark's own tests run on the CPU, outside the repository's
tier-1 suite (``pytest.ini`` collects ``tests/`` only):

    python -m pytest bench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# no persistent compilation cache: CPU programs stay out of the checkout
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[1] / "src", HERE.parents[1] / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
