"""The benchmark's harness. ROOT is the checkout the benchmark runs in:
every file it names is relative to it."""

import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
