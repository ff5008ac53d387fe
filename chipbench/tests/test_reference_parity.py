"""Each configuration of ``BENCHMARK.json`` against its plain reference.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

One case a configuration: its small case (``chipbench/parity/<config>.json``)
run whole, the look for a chip skipped, in a process of its own with the
case's host devices and within the case's seconds; the program's step
agrees with the reference within the case's limits.  A configuration adds
its case by adding that file."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import tinycell  # noqa: E402


def _cases():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        path = tinycell.CASES / f"{c['name']}.json"
        several = path.exists() and tinycell.case(c["name"])["devices"] > 1
        yield pytest.param(c["name"], id=c["name"],
                           marks=[pytest.mark.sharded] if several else [])


@pytest.mark.parametrize("config", list(_cases()))
def test_reference_parity(tmp_path, config):
    out = tinycell.parity(config, tmp_path)
    assert out["correct"] is True, out
    assert out["compiles_in_window"] == 0
    assert set(out["compared"]) == set(tinycell.case(config)["limits"])
    assert list(out)[-1] == "compared"
