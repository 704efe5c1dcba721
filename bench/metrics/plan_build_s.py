"""plan_build_s: host seconds of ``repro.open``, the plan build
(core/plan.py, core/png.py, core/backends.py).  Moves ``setup_s``."""


def read(r):
    return r.counters.get("plan_build_s")
