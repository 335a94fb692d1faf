"""The benchmark's command and its description: ``run.py`` refuses to run
without a TPU or without the program, and ``BENCHMARK.json`` names only
files and readers that exist, in the allowed alphabet."""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _run_command(cwd: pathlib.Path, env: dict) -> subprocess.CompletedProcess:
    cmd = BENCH["command"] + ["--workload", BENCH["workloads"][0]["name"],
                              "--seed", "3", "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable if cmd[0].startswith("python") else cmd[0]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=240)


def _no_result(proc: subprocess.CompletedProcess) -> bool:
    for line in proc.stdout.strip().splitlines()[-1:]:
        try:
            json.loads(line)
            return False
        except ValueError:
            pass
    return True


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run_command(ROOT, env)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "TPU" in proc.stderr


def test_command_fails_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = _run_command(tmp_path, env)
    assert proc.returncode != 0
    assert _no_result(proc)


def test_benchmark_json_names_what_exists():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg.get("reduced", {}))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (ROOT / "benchmarks" / "chip" / "traffic"
                / f"{w['traffic']}.json").is_file()
        reports = [m for m in BENCH["end_to_end"]
                   if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reports) >= 2
        assert any(w["name"] in m["workloads"] for m in BENCH["per_layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
        assert (ROOT / "benchmarks" / "chip" / "metrics"
                / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("kind", ["configs", "workloads"])
def test_names_are_unique(kind):
    names = [x["name"] for x in BENCH[kind]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
