"""The harness finds a kind by importing ``perfbench.kinds.<kind>``. A
kind the tests own (``tests/bench/kinds/``: not under ``perfbench/``, in
no cell) is put into ``sys.modules`` under that name here, which is all
it takes for ``perfbench.kinds.of`` to find it."""

import importlib.util
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

for path in sorted((HERE / "kinds").glob("*.py")):
    name = f"perfbench.kinds.{path.stem}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
