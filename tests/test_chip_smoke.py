"""chip_smoke.py at the boundary a driver sees: the tiny CPU debug run passes and
prints the keys a chip run prints; without the flag, no TPU means no result; and
the compile-cache helper it shares with ReplayEngine places the cache from outside
when told to and at one fixed path in the checkout otherwise."""

import json
import os
import subprocess
import sys

import jax

from surge_tpu.replay import engine as replay_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, cwd):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, SMOKE, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def test_cpu_tiny_run_passes_and_prints_the_chip_run_keys(tmp_path):
    proc = _run("--cpu-tiny", "--seed", "3", cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    keyed = dict(line.split(": ", 1) for line in lines[:-1])
    assert "--cpu-tiny" in keyed["mode"]
    assert keyed["platform"] == "cpu" and keyed["seed"] == "3"
    assert {"device_kind", "device_count", "versions", "compile_cache",
            "reduced", "native"} <= keyed.keys()
    assert set(json.loads(keyed["versions"])) == {"jax", "jaxlib", "libtpu"}
    assert "cold_events" in json.loads(keyed["reduced"])
    for leg in ("cold", "served"):
        facts = json.loads(keyed[f"leg {leg}"])
        assert {"wall_s", "compilations", "compile_s", "cache_hits",
                "peak_bytes_in_use"} <= facts.keys()
        assert facts["compilations"] > 0
    cold, served = (json.loads(keyed[f"leg {leg}"]) for leg in ("cold", "served"))
    assert cold["states_equal_closed_form"] == cold["aggregates"]
    assert len(cold["families"]) == 3
    assert served["gathers"] > 0 and served["lane_errors"] == 0
    assert served["error_signals"] == []
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": keyed["device_kind"],
                               "count": int(keyed["device_count"])}}


def test_without_the_cpu_flag_no_tpu_means_no_result(tmp_path):
    proc = _run(cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "JAX_PLATFORMS='cpu'" in proc.stderr and "'tpu'" in proc.stderr


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(
        tmp_path, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: the helper changes no config
        jax.config.update("jax_compilation_cache_dir", "/placed/by/someone")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
        assert replay_engine.ensure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == "/placed/by/someone"

        # not placed: one absolute path under the checkout, whatever the cwd
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        seen = []
        for cwd in (tmp_path, REPO):
            monkeypatch.chdir(cwd)
            seen.append(replay_engine.ensure_compile_cache())
            assert jax.config.jax_compilation_cache_dir == seen[-1]
        assert seen[0] == seen[1] == os.path.join(REPO, ".jax_cache")
        assert os.path.isabs(seen[0])
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
