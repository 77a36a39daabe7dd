"""The entry points a user runs: ``chip_smoke.py``, the serve CLI, and the
compile-cache placement every entry point shares.

``chip_smoke.py`` refuses a CPU by design, so its phases are driven here
through its own functions at the reduced preset — the same control flow
the chip run takes, at a size the CPU compiles in seconds.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

TINY = dict(reduced=True, batch=4, max_len=64, requests=6, min_prompt=2,
            max_prompt=32, max_new=6)


def _env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    env.update(extra)
    return env


def test_chip_smoke_refuses_the_cpu(tmp_path):
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         capture_output=True, text=True, env=_env(),
                         cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_fails_without_the_repo(tmp_path):
    """Copied out of the checkout, the script has no engine to drive."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((REPO / "chip_smoke.py").read_text())
    env = _env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, str(lone)], capture_output=True,
                         text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_one_chip_phases_on_cpu(tmp_path):
    smoke = chip_smoke.Smoke(store_dir=str(tmp_path / "store"), **TINY)
    assert chip_smoke.run_one_chip(smoke) == []


def test_chip_smoke_oracle_replay_reproduces_the_reference(tmp_path):
    """The logits the oracle check reads at a divergence are the ones the
    batch-of-1 reference decoded its stream from."""
    from repro.launch.serve import ServingEngine
    smoke = chip_smoke.Smoke(store_dir=str(tmp_path / "store"), **TINY)
    eng = ServingEngine(smoke.arch, smoke.config())
    prompt = smoke.prompts(eng.cfg.vocab_size)[0]
    ref = eng.reference_generate(prompt, 5)
    for at in range(len(ref)):
        logits = chip_smoke._reference_logits(eng, prompt, ref, at)
        assert logits.shape == (eng.cfg.vocab_size,)
        assert int(np.argmax(logits)) == ref[at]
    assert eng.reference_generate(prompt, 5) == ref   # oracle still usable


def test_chip_smoke_tensor_parallel_phases_on_four_cpu_devices(tmp_path):
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(REPO)!r})
        import chip_smoke
        smoke = chip_smoke.Smoke(store_dir={str(tmp_path / "store")!r},
                                 **{TINY!r})
        print(json.dumps(chip_smoke.run_four_chips(smoke)))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "every parameter leaf" in out.stdout
    assert "every cache leaf" in out.stdout
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


def test_compile_cache_defers_to_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.use_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first = compile_cache.use_compile_cache()
        assert first == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.use_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_a_cached_executable_keeps_its_own_scopes(monkeypatch, tmp_path):
    """Two programs that differ only in a named scope: the second, compiled
    while the first is in the persistent cache, names its operations by
    its own scope, so a profile reads the program that ran."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.sin(x) * 2
        return jax.jit(f)

    try:
        compile_cache.use_compile_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        x = jax.ShapeDtypeStruct((8,), jnp.float32)
        first = scoped("first").lower(x).compile().as_text()
        assert any(tmp_path.iterdir())          # the first is cached
        second = scoped("second").lower(x).compile().as_text()
        assert "first" in first
        assert "second" in second and "first" not in second
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


@pytest.mark.parametrize("argv,reduced,max_len", [
    ([], True, 128),
    (["--max-len", "64"], True, 64),
])
def test_serve_cli_sizes_the_engine(monkeypatch, capsys, argv, reduced,
                                    max_len):
    """``--max-len`` (and ``--full``) reach the engine config; the
    defaults are unchanged."""
    from repro.launch import serve
    seen = {}
    real = serve.ServingEngine

    def spy(arch, config, **kw):
        seen["config"] = config
        return real(arch, config, **kw)

    monkeypatch.setattr(serve, "ServingEngine", spy)
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["serve", "--requests", "2",
                                      "--batch", "2", "--max-new", "2",
                                      *argv])
    serve.main()
    assert seen["config"].reduced is reduced
    assert seen["config"].max_len == max_len
    assert "'requests': 2" in capsys.readouterr().out


def test_serve_cli_full_flag_selects_published_widths(monkeypatch):
    from repro.launch import serve
    seen = {}

    class Stop(Exception):
        pass

    def spy(arch, config, **kw):
        seen["config"] = config
        raise Stop

    monkeypatch.setattr(serve, "ServingEngine", spy)
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["serve", "--full", "--max-len",
                                      "2048"])
    with pytest.raises(Stop):
        serve.main()
    assert seen["config"].reduced is False
    assert seen["config"].max_len == 2048
