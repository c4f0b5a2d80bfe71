"""Tier-1 collects the benchmark's own tests beside ``tests/``.

The tier-1 command names ``tests/`` only, and every verdict rests on what
``benchmark/tests`` guards (``correct``, the window, the trace reduction),
so a run over the whole of ``tests/`` takes them in too.  A run of single
files or of ``benchmark/tests`` itself is left as given; xdist workers
receive the amended arguments and add nothing.
"""

import os

ROOT = os.path.dirname(os.path.abspath(__file__))


def pytest_configure(config):
    base = str(config.invocation_params.dir)
    given = {os.path.normpath(os.path.join(base, a)) for a in config.args}
    benchmark_tests = os.path.join(ROOT, "benchmark", "tests")
    if os.path.join(ROOT, "tests") in given and benchmark_tests not in given:
        config.args.append(benchmark_tests)
