"""The command and ``BENCHMARK.json``: no chip means no result, and every
name in the file has the files the harness finds by that name."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, run

ROOT = harness.REPO
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _cli(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=240)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _cli("bench/run.py", "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
             "--seconds", "10", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_workload_fails():
    p = _cli("bench/run.py", "--workload", "no-such-cell", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    assert len({m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}) == \
        len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


def test_metrics_have_readers_and_bounds():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(w):
    configs = {c["name"]: c for c in BENCH["configs"]}
    c = configs[w["config"]]
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["name"] == c["name"] and c["source"] == conf["source"]
    assert set(c["reduced"]) == set(conf["reduced"]) <= set(conf["config"])
    assert w["chips"] == 1
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    limits = json.loads((ROOT / "bench" / "limits" / f"{w['name']}.json").read_text())["limits"]
    assert limits and all(v > 0 for v in limits.values())
    assert run.metric_specs(BENCH, w["name"], False) and run.metric_specs(BENCH, w["name"], True)


def test_program_configs_agree_with_the_files():
    for c in BENCH["configs"]:
        harness.program_config(harness.load_config(c["name"]))
