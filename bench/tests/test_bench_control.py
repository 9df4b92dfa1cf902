"""The control at a test size: the float32 reference with its weight
matrices rounded to the precision below the configuration's (bf16 for this
float32 configuration; fp8 as a coarser one), read at the served
positions, comes out not correct through the same ``check.readings`` and
``check.correct`` that decide a run, where the program's own run is
correct."""
import json
from pathlib import Path

import pytest

import control
import fleet as fleet_mod
import traffic

DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((DATA / "bench.json").read_text())


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(fleet_mod, "CONFIG_DIR", DATA / "configs")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA / "traffic")


def test_control_fails_where_the_program_passes(tiny):
    cell = {w["name"]: w for w in BENCH["workloads"]}["tiny-mix.chat"]
    seeds = [11, 12, 13]
    rows = control.readings(cell, seeds, 2.0, ["bf16", "fp8"],
                            need_chip=False)
    limits = json.loads((DATA / "configs" / "tiny-mix.json").read_text()
                        )["limits"]
    summary = control.summary(rows, ["bf16", "fp8"])
    assert summary["correct_on"] == {"program": seeds, "bf16": [],
                                     "fp8": []}
    for model in limits:
        s, lim = summary[f"logit_gap.{model}"], limits[model]["logit_gap"]
        assert s["program_max"] <= lim
        assert s["fp8_min"] > lim
    for r in rows:
        assert r["failed"] == 0
