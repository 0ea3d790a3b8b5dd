"""The benchmark's own tests: CPU only, no device metric is read here.

``bench.py`` at the root of the repository owns the module name ``bench``,
so the benchmark's modules are imported under the alias ``mxbench``, which
``bench/run.py`` sets up (see ``alias_package`` there). This directory is
searched first, as in a rehearsal: the second family's builder, reference and
count live here (``models/``, ``reference/``, ``work/``)."""
import importlib.util
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)   # the program: mxnet_tpu

_spec = importlib.util.spec_from_file_location(
    "mxbench_run", os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(_spec)
sys.modules["mxbench_run"] = run
_spec.loader.exec_module(run)
run.alias_package(os.path.join(BENCH, "tests"))
