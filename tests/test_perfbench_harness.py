"""perfbench/tests/test_harness.py in tier-1: the benchmark harness is checked
against the program on every change, not only when the benchmark is run.
One module per harness test file, so that the workers share them out."""

from perfbench.tests.test_harness import *  # noqa: F401,F403
