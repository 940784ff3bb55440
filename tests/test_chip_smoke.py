"""chip_smoke.py stays runnable: the --tiny form passes on CPU (same legs, toy
width), the real form refuses a machine without a TPU, and the compile-cache
helper places the cache where the contract says."""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_tiny_passes_on_cpu_and_real_form_refuses(tmp_path):
    cache = tmp_path / "cache"
    # placed from outside, thresholds too: toy CPU programs compile in under
    # JAX's default 1 s floor and would otherwise leave the directory empty
    placed = {"JAX_COMPILATION_CACHE_DIR": str(cache),
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    proc = _run(["--tiny"], placed)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == len(jax.devices())
    # exactly one JSON line, every leg reported (d runs on the conftest mesh)
    assert sum(ln.startswith("{") for ln in lines) == 1
    for leg in ("leg a", "leg b", "leg c", "leg d"):
        assert any(leg in ln for ln in lines), f"{leg} missing:\n{proc.stdout}"
    # the placed directory is where the cache filled
    assert f"compile cache: {cache}" in proc.stdout
    assert any(cache.iterdir())

    proc = _run([], placed, timeout=120)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_compile_cache_helper_placement(monkeypatch):
    from glint_word2vec_tpu.compile_cache import enable_compile_cache

    knobs = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in knobs}
    try:
        # placed from outside: JAX reads the variable itself, code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert enable_compile_cache() == "/somewhere/else"
        assert (jax.config.jax_compilation_cache_dir
                == saved["jax_compilation_cache_dir"])

        # unset: a fixed path under the checkout, the same on every call
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == want
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
