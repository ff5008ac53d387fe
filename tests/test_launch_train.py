"""The launcher loop that the CLI and ``chip_smoke.py`` share
(``repro.launch.train.run``), at a tiny size on the CPU."""

import math
import signal

import pytest

from repro import obs
from repro.launch import train
from repro.launch.mesh import parse_mesh


@pytest.mark.parametrize("spec,want", [("1x1", (1, 1)), ("4x1", (4, 1)),
                                       ("2X4", (2, 4))])
def test_parse_mesh(spec, want):
    assert parse_mesh(spec) == want


@pytest.mark.parametrize("spec", ["4", "0x1", "ax1", "1x2x3"])
def test_parse_mesh_rejects(spec):
    with pytest.raises(ValueError):
        parse_mesh(spec)


def test_run_reports_each_step():
    before = signal.getsignal(signal.SIGTERM)
    out = train.run(train.parse_args(
        ["--arch", "mamba2-370m", "--steps", "3", "--batch", "2",
         "--seq", "32"]))
    assert signal.getsignal(signal.SIGTERM) == before
    assert out["compile_s"] > 0.0
    assert len(out["losses"]) == len(out["step_s"]) == 3
    assert all(math.isfinite(x) for x in out["losses"])
    # cold start: empty histograms give zero thresholds, everything is
    # selected; then the warm thresholds hold it near rho = 0.1
    assert out["sel_frac"][0] == 1.0
    assert all(0.0 < s <= 0.5 for s in out["sel_frac"][1:])
    # the host spans of each step: every phase, one entry per step
    assert tuple(out["host_ms"]) == train.HOST_PHASES
    assert all(len(v) == 3 and all(x >= 0.0 for x in v)
               for v in out["host_ms"].values())
    assert out["host_ms"]["ckpt"] == [0.0] * 3       # no --ckpt-every
    assert all(b > 0.0 for b in out["host_ms"]["block"])


def test_run_times_each_step_by_host_span(tmp_path):
    obs.reset()
    out = train.run(train.parse_args(
        ["--arch", "mamba2-370m", "--steps", "2", "--batch", "2",
         "--seq", "32", "--ckpt-every", "1", "--ckpt-dir", str(tmp_path)]))
    spans = obs.spans()
    assert [s.name for s in spans if s.name == "server_init"] == [
        "server_init"]
    steps = [s for s in spans if s.name in train.HOST_PHASES]
    assert [s.name for s in steps] == list(train.HOST_PHASES) * 2
    assert all(s.parent is None for s in steps)
    for i, phase in enumerate(train.HOST_PHASES):
        assert out["host_ms"][phase] == [steps[i].ms,
                                         steps[i + len(train.HOST_PHASES)].ms]
    assert all(x > 0.0 for x in out["host_ms"]["ckpt"])
    # dispatch and block are the two spans inside step_s
    assert all(a + b <= s * 1e3 + 1.0 for a, b, s in zip(
        out["host_ms"]["dispatch"], out["host_ms"]["block"], out["step_s"]))
    obs.reset()


def test_run_rejects_a_mesh_larger_than_the_host():
    with pytest.raises(ValueError, match="devices"):
        train.run(train.parse_args(["--arch", "mamba2-370m", "--steps", "1",
                                    "--mesh", "64x1"]))
