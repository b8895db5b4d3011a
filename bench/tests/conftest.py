"""The benchmark's own tests: ``pytest bench/tests``, on the CPU."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from harness import common  # noqa: E402

# compiles on the CPU are not worth keeping, and XLA warns about each
# entry it reads back on another CPU
jax.config.update("jax_enable_compilation_cache", False)


def config(name: str) -> dict:
    """A benchmark configuration, or one kept under ``data/`` for a cell
    still to come (``mamba2-130m``: its reference and work functions are
    in place and tested, its cell is not)."""
    path = pathlib.Path(__file__).parent / "data" / f"{name}.json"
    return common.load_json(path) if path.exists() else common.config(name)
