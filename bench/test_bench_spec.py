"""``BENCHMARK.json`` finds a file for everything it names, and a run that
finds no TPU refuses, printing no result."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import workload

ROOT = Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_the_file_keeps_to_its_form():
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert set(SPEC) == {"command", "paths", "run_seconds", *ENTRY_KEYS}
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(_line(w) for w in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/")
        assert ".." not in p.split("/")
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    for key, (need, may) in ENTRY_KEYS.items():
        entries = SPEC[key]
        assert entries and len({e["name"] for e in entries}) == len(entries)
        for e in entries:
            assert need <= set(e) <= need | may, (key, e["name"])
            assert NAME.fullmatch(e["name"])
    for c in SPEC["configs"]:
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.fullmatch(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert _line(m["layer"])


def test_every_name_has_its_file():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in configs.values():
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
        t = body["config"]["model_type"]
        assert (ROOT / "bench" / "families" / f"{t}.py").is_file()
        assert (ROOT / "bench" / "reference" / f"{t}.py").is_file()
    for cell in SPEC["workloads"]:
        assert cell["config"] in configs
        workload.check(json.loads((ROOT / "bench" / "traffic"
                                   / f"{cell['traffic']}.json").read_text()))
        limit = json.loads((ROOT / "bench" / "limits"
                            / f"{cell['name']}.json").read_text())
        assert limit["logit_gap"] > 0
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_enough():
    cells = [c["name"] for c in SPEC["workloads"]]

    def mine(m, cell):
        return "workloads" not in m or cell in m["workloads"]
    for cell in cells:
        e2e = [m["name"] for m in SPEC["end_to_end"] if mine(m, cell)]
        assert "setup_s" in e2e and len(e2e) >= 2
        moved = [m for m in SPEC["per_layer"] if mine(m, cell)]
        assert moved and all(m["moves"] in e2e for m in moved)


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_a_run_without_a_tpu_prints_no_result(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", cell, "--seed", str(2**31 + 1),
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()
