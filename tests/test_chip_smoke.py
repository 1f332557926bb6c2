"""Rehearsal of ``chip_smoke.py`` on the CPU at a tiny size: its phases run
here through the same gateway -> router -> replica -> engine path, with the
Pallas kernels of the logits check in interpret mode. The script itself
refuses the CPU; the tests call its functions instead."""
import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.configs import tiny_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
TINY_ENGINE = dict(max_slots=4, page_size=8, num_pages=64, max_seq=96,
                   prefill_chunk=16, greedy=True, cache_dtype=jnp.bfloat16)
TINY_RUN = dict(dtype=jnp.bfloat16, n_requests=4, prompt_len=(20, 60), max_new=4)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], capture_output=True, text=True,
                          timeout=600, env=env, cwd=REPO)


def test_refuses_a_host_without_tpu():
    r = _run([SCRIPT])
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_config_is_mixtral_at_published_widths(chip_smoke):
    cfg = chip_smoke.smoke_config()
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.vocab) == (2, 4096, 32, 8, 128, 32000)
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.d_expert) == (8, 2, 14336)


def test_one_chip_phases(chip_smoke, capsys):
    reqs = chip_smoke.run_one_chip(tiny_config("mixtral-8x7b"), TINY_ENGINE, **TINY_RUN)
    assert [len(r.generated) for r in reqs] == [4] * 4
    out = capsys.readouterr().out
    assert "logits pallas vs xla" in out and "served 4 requests" in out


def test_four_replica_phase_on_virtual_devices():
    code = "\n".join([
        "import jax.numpy as jnp, chip_smoke as cs",
        "from repro.configs import tiny_config",
        f"kw = dict({dict(TINY_ENGINE, cache_dtype=None)!r}, cache_dtype=jnp.bfloat16)",
        "cs.run_four_chips(tiny_config('mixtral-8x7b'), kw, dtype=jnp.bfloat16,",
        "                  n_requests=4, prompt_len=(20, 60), max_new=4)"])
    r = _run(["-c", code], {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                            "PYTHONPATH": REPO})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "4 of 4 requests identical to one replica" in r.stdout
    for i in range(4):
        assert f"r{i} params+pool on ['TFRT_CPU_{i}']" in r.stdout


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(tmp_path, env_dir):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / env_dir)} if env_dir else {}
    code = ("import json, jax; from repro.launch.compile_cache import enable_compile_cache;"
            "d = enable_compile_cache();"
            "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))")
    r = _run(["-c", code], dict(env, PYTHONPATH=os.path.join(REPO, "src")))
    assert r.returncode == 0, r.stderr[-2000:]
    ret, configured = json.loads(r.stdout.strip().splitlines()[-1])
    want = str(tmp_path / env_dir) if env_dir else os.path.join(REPO, ".jax_cache")
    assert ret == configured == want
