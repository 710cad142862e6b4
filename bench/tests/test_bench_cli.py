"""The command refuses to run anywhere but on the chip it was given, and
BENCHMARK.json names only files that exist."""

import json
import os
import subprocess
import sys
import types

from bench import run as R


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(R.BENCH / "run.py"), "--workload",
         "bm25-w1-steady", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=R.ROOT)
    assert r.returncode == 2
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_unknown_chip_or_too_few_chips_is_an_error(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    assert R.find_chip(1) is None
    fake.device_kind = "TPU v5 lite"
    assert R.find_chip(4) is None
    dev, peak = R.find_chip(1)
    assert dev is fake and peak["hbm_bytes_per_s"] == 819e9


def test_every_name_in_benchmark_json_has_its_file():
    spec = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((R.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in spec["workloads"]:
        assert (R.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        cell = R.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
    for m in spec["per_layer"]:
        assert callable(R.load_reader(m["name"]))
