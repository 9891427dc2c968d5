"""``chip_smoke.py`` on the CPU: its phases at ``reduce_config`` size with
the Pallas kernels in interpret mode, its refusal to run without a TPU, and
the compile-cache helper its entry point calls."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduce_config
from repro.launch import compile_cache
from repro.models import model as M
from repro.serving import Engine, EngineConfig

ROOT = Path(__file__).resolve().parents[1]
SERVE = dict(max_batch=4, max_len=128, page_size=16, chunk_tokens=16)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def model():
    """A bf16 smoke-size olmo, so the bf16 tolerance is what is tested."""
    cfg = reduce_config(get_config("olmo-1b")).with_(
        compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    return cfg, M.init(cfg, jax.random.PRNGKey(0))


def _prompts(smoke, cfg):
    return smoke.make_prompts(cfg.vocab_size, 0, n=4, lens=(8, 40),
                              shared=20, page_size=SERVE["page_size"])


def test_make_prompts_share_a_cow_prefix(smoke):
    ps = smoke.make_prompts(50304, 0)
    assert len(ps) == smoke.N_REQUESTS
    assert all(64 <= len(p) <= 768 for p in ps)
    assert ps[0][:200] == ps[1][:200] and ps[0][200] != ps[1][200]
    assert len(ps[0]) >= 256  # fills the page the shared prefix ends in
    assert ps == smoke.make_prompts(50304, 0)  # seeded


def test_logit_tol_olmo_bf16(smoke):
    assert smoke.logit_tol(get_config("olmo-1b")) == pytest.approx(
        0.0884, abs=1e-4)  # 4 * sqrt(2 * 16) * 2**-8


def test_one_chip_phases_interpret(smoke, model, capsys):
    cfg, params = model
    smoke.one_chip(cfg, params, _prompts(smoke, cfg), 0, mode="interpret",
                   serve_kw=SERVE, max_new=4)
    out = capsys.readouterr().out
    assert "prefix_hit_tokens=" in out and "prefix_hit_tokens=0 " not in out
    assert out.count("rel_rms=") == 2


def test_check_kernels_refuses_interpret_program(smoke, model):
    cfg, params = model
    eng = Engine(cfg, params, EngineConfig(kernel_mode="interpret", **SERVE))
    hlo = smoke.tick_hlo(eng)
    assert set(hlo) == {"mixed_step", "decode_chunk"}
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        smoke.check_kernels(hlo)


def test_compare_logits_refuses_a_wrong_answer(smoke, model):
    cfg, params = model
    good = jnp.ones((2, 8))
    assert smoke.compare_logits("same", good, good, 1e-6) == 0.0
    with pytest.raises(AssertionError, match="rel_rms"):
        smoke.compare_logits("off", good * 1.5, good, smoke.logit_tol(cfg))


FOUR = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util, sys
    import jax, jax.numpy as jnp
    from repro.configs import get_config, reduce_config
    from repro.models import model as M
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg = reduce_config(get_config("olmo-1b")).with_(
        compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = smoke.make_prompts(cfg.vocab_size, 0, n=4, lens=(8, 40),
                                 shared=20, page_size=16)
    smoke.four_chips(cfg, params, prompts, 0, mode="interpret",
                     serve_kw=dict(max_batch=4, max_len=128, page_size=16,
                                   chunk_tokens=16), max_new=4)
    print("FOUR-CHIPS-OK")
""")


def test_four_chips_phase_on_host_devices():
    """The ``--chips 4`` path (mesh 1x4 against device 0, interpret
    kernels under shard_map) on four forced host devices, in a child
    process so this one keeps its single device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", FOUR,
                          str(ROOT / "chip_smoke.py")], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "FOUR-CHIPS-OK" in res.stdout, res.stdout + res.stderr[-3000:]
    assert "logits mesh 1x4 vs device 0: rel_rms=" in res.stdout


def _run_entry(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_a_cpu():
    res = _run_entry(ROOT / "chip_smoke.py", ROOT)
    assert res.returncode != 0
    assert "platform=cpu" in res.stdout
    assert '"ok": true' not in res.stdout


def test_entry_point_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    res = _run_entry(tmp_path / "chip_smoke.py", tmp_path)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


@pytest.fixture
def cache_config():
    """Restore JAX's cache settings after a test changes them."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache as cc
    cc.reset_cache()


def _compile_something(n: int):
    jax.jit(lambda x: jnp.tanh(x) * n + 1.0).lower(
        jnp.zeros((n, 3))).compile()


def test_compile_cache_env_var_wins(tmp_path, monkeypatch, cache_config):
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    _compile_something(7)
    assert any(tmp_path.iterdir()), "no entry was written to the cache"


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert path == compile_cache.compile_cache_dir()  # no pid, time, temp
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
