"""Where the persistent compilation cache lives (utils/platform.py): at
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it — the code then
sets no directory at all — and otherwise at one fixed, git-ignored path
inside the checkout. The path is part of the cache key, so nothing that
changes between runs (pid, time, temp name, host digest) may be in it."""
import os
import pathlib

import jax

from elasticsearch_tpu.utils import platform

REPO = pathlib.Path(__file__).resolve().parents[2]


def _recorded_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_env_placed_cache_sets_no_directory_in_code(monkeypatch, tmp_path):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    platform.enable_compilation_cache()
    assert calls, "the cache thresholds are still configured"
    assert "jax_compilation_cache_dir" not in [k for k, _ in calls]


def test_default_cache_is_one_fixed_path_in_the_checkout(monkeypatch):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    platform.enable_compilation_cache()
    dirs = [v for k, v in calls if k == "jax_compilation_cache_dir"]
    assert dirs == [str(REPO / ".jax_cache")]
    assert platform.host_fingerprint() not in dirs[0]
    # a second process resolves the very same path
    assert dirs[0] == platform.COMPILATION_CACHE_DIR
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_no_other_code_sets_a_cache_directory():
    setters = []
    for root in ("elasticsearch_tpu", "tools", "tests"):
        for path in (REPO / root).rglob("*.py"):
            if path == pathlib.Path(__file__).resolve():
                continue
            if "jax_compilation_cache_dir" in path.read_text():
                setters.append(str(path.relative_to(REPO)))
    for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
        if "jax_compilation_cache_dir" in (REPO / name).read_text():
            setters.append(name)
    assert setters == [os.path.join("elasticsearch_tpu", "utils",
                                    "platform.py")]
