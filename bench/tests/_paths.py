"""Puts the benchmark's directory and the program's sources on sys.path."""
import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def bench_run():
    """The harness's entry module (bench/run.py), imported once."""
    if "bench_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location("bench_run",
                                                      BENCH / "run.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_run"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["bench_run"]
