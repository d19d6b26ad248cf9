"""chip_smoke.py's contract as far as a CPU can show it: without a TPU and
without the rehearsal flag it fails and says why, printing no result; the
labelled CPU rehearsal drives the whole path — two server generations over
one data path, the width child, the kernels interpreted — and passes."""
import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]


def _run(tmp_path, *flags, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one CPU device, one shard
    # the cache is placed from outside, where the run can be thrown away
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"),
         "--out", str(tmp_path / "out"), "--work", str(tmp_path / "work"),
         *flags],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(tmp_path))


def test_refuses_cpu_without_the_rehearsal_flag(tmp_path):
    p = _run(tmp_path, "--docs", "256", timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == "", "a failed run prints no result"
    assert "JAX found no accelerator" in p.stderr
    assert "[cpu]" in p.stderr
    result = json.loads((tmp_path / "out" / "result.json").read_text())
    assert result["ok"] is False and result["claim"] is None
    assert not (tmp_path / "work").exists(), "data path is removed"


def test_cpu_rehearsal_passes_end_to_end(tmp_path):
    p = _run(tmp_path, "--rehearse-cpu", "--docs", "2048",
             "--width-docs", "16384", timeout=840)
    assert p.returncode == 0, p.stderr[-4000:]
    report_line, last = p.stdout.strip().splitlines()[-2:]
    # the last line is the contract's object: these two keys and no other
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert report_line.startswith("SMOKE_REPORT ")
    result = json.loads(report_line[len("SMOKE_REPORT "):])
    assert result["ok"] is True
    assert result["device"] == json.loads(last)["device"]
    assert list(result)[-1] == "claim" and result["claim"] is None
    assert result["load"]["loaded_by"] == "_bulk"
    assert result["load"]["docs"] == 2048 and result["reduced"]
    for server in ("server_1", "server_2"):
        k = result[server]["counters"]["kernels"]
        assert k["mesh_search"] > 0 and k.get("mesh_fallback_total", 0) == 0
    # the restart verdict: nothing compiled at full price in the second
    # process, from its census replay at boot to its exit
    second = result["server_2"]
    cache = second["counters"]["compile_cache"]
    assert second["fresh_at_boot"] == second["fresh_total"] == 0
    assert cache["fresh"] == 0 and cache["aot_hit"] + cache["xla_dir_hit"] > 0
    for server in ("server_1", "server_2"):
        conc = result[server]["requests"]["concurrent_64"]
        assert conc["n"] == 64 and conc["fused_batches"] >= 1
    width = result["width"]
    assert width["loaded_by"] == "segment_loader" and width["interpreted"]
    assert set(width["kernels"]) == {
        "knn_topk Q=8 k=64", "knn_topk Q=256 k=40", "adc_scores",
        "maxsim_adc"}
