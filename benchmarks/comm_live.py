"""Plan-counted vs model communication rows (DESIGN.md §14).

Where fig8_comm.py reports the paper's ANALYTIC byte model (eqs. 3-5)
and the compiled HLO's "bytes accessed", this job reports the bytes
counted off the plan's REAL array geometry — the padded streams the
backends actually bind (``obs.comm.measure_plan``).

Two rows per (dataset, method):

  comm/<ds>/<m>/measured — DRAM-model bytes/iteration off the plan
                           geometry (padding included, on-chip bins
                           traffic reported in ``derived`` separately)
  comm/<ds>/<m>/model    — the paper's eq. 3-5 prediction at the
                           plan's measured r, plus measured/model ratio

The pcpm measured/model ratio stays within 2x at scale 16; the gap's
composition — schedule padding, the bins write + read round trip
eq. 5 folds into 1/r terms — is quantified in DESIGN.md §14.
"""
from __future__ import annotations

from repro.core.plan import PlanConfig, build_plan
from repro.obs import vs_model

from .common import Csv, Dataset

METHODS = ("pcpm", "pdpr", "bvgas")


def run(datasets: list[Dataset], *, part_size: int = 65536) -> Csv:
    csv = Csv()
    for ds in datasets:
        for method in METHODS:
            plan = build_plan(ds.graph, PlanConfig(method=method,
                                                   part_size=part_size))
            cmp_ = vs_model(plan)
            csv.add(f"comm/{ds.name}/{method}/measured", 0.0,
                    f"B/iter={cmp_['measured_bytes_per_iter']:.0f},"
                    f"B/edge={cmp_['measured_bytes_per_iter'] / ds.m:.2f},"
                    f"onchip={cmp_['measured_onchip_bytes']:.0f}")
            derived = (f"B/iter={cmp_['model_bytes_per_iter']:.0f},"
                       f"ratio={cmp_['ratio']:.2f},r={cmp_['r']:.2f}")
            if "model_bytes_per_iter_best" in cmp_:
                derived += (f",best={cmp_['model_bytes_per_iter_best']:.0f}")
            csv.add(f"comm/{ds.name}/{method}/model", 0.0, derived)
    return csv


def summarize(rows) -> dict:
    """Fold comm/ rows into the JSON summary block: per dataset, per
    method, measured vs model bytes/iteration and their ratio."""
    summ: dict = {}

    def _field(derived, key):
        for part in derived.split(","):
            if part.startswith(key + "="):
                return float(part.split("=", 1)[1])
        return None

    for n, _us, derived in rows:
        if not n.startswith("comm/"):
            continue
        _, ds_name, method, kind = n.split("/")
        e = summ.setdefault(ds_name, {}).setdefault(method, {})
        if kind == "measured":
            e["measured_bytes_per_iter"] = _field(derived, "B/iter")
        elif kind == "model":
            e["model_bytes_per_iter"] = _field(derived, "B/iter")
            e["ratio"] = _field(derived, "ratio")
            e["r"] = _field(derived, "r")
    return summ
