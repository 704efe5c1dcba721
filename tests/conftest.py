"""Set-up shared by every test directory."""
import os

# The benchmark's CPU tests (tests/bench/conftest.py) run each
# configuration of BENCHMARK.json cut to the tiny scale its
# ``TINY_SCALE`` table names; that table lists the configurations it
# was written with, and configurations added since take their tiny
# scale from here.
TINY_SCALE = {"graphalytics-pr.graph500-22.hybrid": 10}


def pytest_plugin_registered(plugin, manager):
    path = getattr(plugin, "__file__", None) or ""
    if path.endswith(os.path.join("tests", "bench", "conftest.py")):
        for name, scale in TINY_SCALE.items():
            plugin.TINY_SCALE.setdefault(name, scale)
