"""One process per chip, one place for compiled programs, no stand-ins
under a kernel's name — the parts of the chip bring-up that need no chip:
the launcher's chip assignment, the compile-cache helper and the engine's
refusal of a paged kernel it cannot run."""

import json
import os
import subprocess
import sys

import jax
import pytest

from horovod_tpu import launcher
from horovod_tpu.utils import chips

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP_VARS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
              "TPU_PROCESS_BOUNDS")


def _envs(monkeypatch, cpu, n=4, chips_on_host="4"):
    monkeypatch.setenv("HVD_CHIPS_PER_HOST", chips_on_host)
    return [launcher._rank_env(
        r, launcher._local_rank(r, cpu=cpu, cpu_world=n), n,
        "127.0.0.1:1", cpu=cpu) for r in range(n)]


def test_np4_gives_each_child_its_own_single_chip(monkeypatch):
    envs = _envs(monkeypatch, cpu=False)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["HVD_LOCAL_RANK"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_cpu_world_sets_no_chip(monkeypatch):
    for var in _CHIP_VARS:
        monkeypatch.delenv(var, raising=False)
    envs = _envs(monkeypatch, cpu=True)
    for e in envs:
        assert e["JAX_PLATFORMS"] == "cpu"
        assert not any(v in e for v in _CHIP_VARS)
    assert [e["HVD_RANK"] for e in envs] == ["0", "1", "2", "3"]


@pytest.mark.parametrize("chips_on_host,message", [
    ("2", "needs its own chip"),            # more ranks than chips
    ("", "found no TPU device node"),       # no chip found, no --cpu
], ids=["more-ranks-than-chips", "no-device-node"])
def test_launcher_refuses_instead_of_sharing_a_chip(monkeypatch,
                                                    chips_on_host, message):
    monkeypatch.setenv("HVD_CHIPS_PER_HOST", chips_on_host)
    monkeypatch.setattr(chips.glob, "glob", lambda pattern: [])
    with pytest.raises(SystemExit, match=message):
        launcher._local_rank(3, cpu=False, cpu_world=4)


_PROBE = ("import json, os; "
          "from horovod_tpu.utils.chips import enable_compile_cache; "
          "import jax; "
          "before = jax.config.jax_compilation_cache_dir; "
          "ret = enable_compile_cache(); "
          "print(json.dumps({'ret': ret, 'before': before, "
          "'config': jax.config.jax_compilation_cache_dir, "
          "'env': os.environ.get('JAX_COMPILATION_CACHE_DIR'), "
          "'metadata_in_key': [jax.config."
          "jax_compilation_cache_include_metadata_in_key, os.environ.get("
          "'JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY')]}))")


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd="/",
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_helper_leaves_a_set_variable_alone(tmp_path):
    want = str(tmp_path / "outside")
    got = _probe(want)
    # jax read the variable itself; the helper set nothing else in code.
    # An executable is profiled by its metadata (named scopes), so the
    # key keeps it, for this process and its children.
    assert got.pop("metadata_in_key") == [True, "1"]
    assert got == {"ret": want, "before": want, "config": want, "env": want}


def test_cache_helper_fixed_checkout_path_when_unset():
    a, b = _probe(None), _probe(None)           # two processes
    fixed = os.path.join(ROOT, ".jax_compile_cache")
    for got in (a, b):
        assert got["before"] is None
        assert got["ret"] == got["config"] == got["env"] == fixed
    assert chips.COMPILE_CACHE_DIR == fixed     # no temp name, pid or time


def test_engine_refuses_paged_kernel_it_cannot_run(monkeypatch):
    """Asked for and unable to run (a real TPU needs d_head % 128 == 0):
    the engine raises — it never serves the gather path under the
    kernel's name."""
    import jax.numpy as jnp
    from horovod_tpu import serve
    from horovod_tpu.parallel.transformer import (TransformerConfig,
                                                  init_params)
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=32, dtype=jnp.float32,
                            unembed_dtype=jnp.float32, attn_backend="xla")
    params = init_params(jax.random.PRNGKey(0), cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="paged_kernel=True cannot run"):
        serve.GenerationEngine(params, cfg, serve.GenerationConfig(
            max_slots=2, max_len=16, kv_layout="paged", paged_kernel=True))
