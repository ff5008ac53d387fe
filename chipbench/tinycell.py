"""One configuration's cell at a small size, run whole on the CPU.

A configuration's small case is ``chipbench/parity/<config>.json``: the
model keys it changes, its traffic, the host devices it runs on, the
seconds a run of it may take, and its limits, set like a cell's from its
own readings.  ``make_root`` writes a checkout that holds that one cell
(``CELL``), found by name as a benchmark cell is; ``run`` drives a whole
run of it, the look for a chip skipped, with the program's step or one
that a test hands it; ``in_subprocess`` runs code in a process with that many host
devices, so that the caller keeps its own one device."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
import time
import traceback
from pathlib import Path
from typing import Any, Dict

from chipbench import flops, harness

ROOT = Path(__file__).resolve().parent.parent
CASES = ROOT / "chipbench" / "parity"
CELL = "tiny.t"
SEED = 2 ** 33 + 5


def case(config: str) -> Dict[str, Any]:
    return json.loads((CASES / f"{config}.json").read_text())


def make_root(tmp: Path, config: str, flags=None, limits=None) -> Path:
    """A checkout at ``tmp`` holding ``config``'s small case as its one
    cell, with every metric of the benchmark; ``flags`` and ``limits``
    replace the case's server flags and limits."""
    c = case(config)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {e["name"]: e for e in bench["configs"]}[config]
    bench["configs"] = [dict(entry, name="tiny",
                             file="chipbench/configs/tiny.json")]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "t",
                           "chips": c["devices"],
                           "why": f"{config}'s small case on the CPU"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    base = tmp / "chipbench"
    for d in ("configs", "traffic", "limits", "metrics"):
        (base / d).mkdir(parents=True)
    for f in (ROOT / "chipbench/metrics").glob("*.py"):
        shutil.copy(f, base / "metrics")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    conf = json.loads((ROOT / entry["file"]).read_text())
    conf["model"] = dict(conf["model"], **c["model"])
    conf["params"] = flops.reference(conf["reference"]).param_count(
        conf["model"])
    (base / "configs/tiny.json").write_text(json.dumps(conf))
    traffic = dict(c["traffic"])
    if flags is not None:
        traffic["server_flags"] = list(flags)
    (base / "traffic/t.json").write_text(json.dumps(traffic))
    (base / f"limits/{CELL}.json").write_text(
        json.dumps(c["limits"] if limits is None else limits))
    return tmp


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------

def run(root: Path, program_cls=harness.Program) -> Dict[str, Any]:
    """A whole run of the root's one cell on this process's devices, the
    look for a chip skipped, its step built by ``program_cls``; a run that
    raises reads as not correct, with the exception as ``error`` and the
    end of its traceback as ``traceback``."""
    cell = harness.load_cell(CELL, root)
    device = {"platform": "cpu", "kind": "cpu", "count": cell.chips}
    try:
        return harness.run_cell(cell, SEED, 0.5, False, device,
                                time.perf_counter(), program_cls=program_cls)
    except Exception as err:                      # noqa: BLE001
        return {"correct": False, "error": repr(err),
                "traceback": traceback.format_exc()[-4000:],
                "compared": {}}


def in_subprocess(code: str, devices: int, timeout: float) -> dict:
    """The JSON object ``code`` prints last, run in a process of its own
    with ``devices`` host devices, within ``timeout`` seconds."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout, cwd=str(ROOT))
    if p.returncode:
        raise RuntimeError(p.stderr[-4000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def parity(config: str, tmp: Path) -> Dict[str, Any]:
    """A sound run of ``config``'s small case against its plain reference,
    on the case's host devices, within the case's seconds."""
    c = case(config)
    root = make_root(tmp, config)
    return in_subprocess(f"""
        import json
        from pathlib import Path
        from chipbench import tinycell
        print(json.dumps(tinycell.run(Path({str(root)!r}))))
    """, c["devices"], c["seconds"])
