"""``chip_smoke.py`` on the CPU: its kernel and serving phases at the
``reduced()`` size with the kernels interpreted, its refusal to report
success without a TPU, and the entry points' compile-cache helper."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # its dataclass resolves through it
    spec.loader.exec_module(mod)
    mod._import_repro()
    return mod


@pytest.fixture(scope="module")
def plan(cs):
    # 4 requests of 8-40 tokens on 2 slots: two join waves, at the 64
    # and 32 buckets
    return cs.Plan(reduced=True, batch=2, max_len=64, page_size=8,
                   requests=4, prompt_range=(8, 40), max_new=4,
                   speculate_k=2, prefill_lqs=(16, 40), widths=(32, 64),
                   attn_mode="kernel")


def test_kernel_phase_on_cpu(cs, plan):
    from repro.configs import get_config
    cs.check_kernels(get_config(cs.ARCH).reduced(), plan, interpret=True)


def test_serve_phase_on_cpu(cs, plan, capsys):
    outs = cs.serve_phase(plan, interpret=True, on_tpu=False)
    assert set(outs) == {"dense", "paged", "paged+spec2", "paged/xla"}
    assert outs["paged"]["routes"].keys() == {"decode.kernel",
                                              "prefill.kernel"}
    assert outs["paged/xla"]["routes"].keys() == {"decode.xla",
                                                  "prefill.xla"}
    said = capsys.readouterr().out
    assert "logits kernel vs xla (dense)" in said
    assert "logits kernel vs xla (paged)" in said


def test_main_exits_nonzero_without_tpu(cs, capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_exits_nonzero(tmp_path):
    """Copied away from the repository, the script fails before it
    touches JAX and prints no result."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX's own


def test_compile_cache_default_is_fixed_in_repo(monkeypatch):
    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = enable_compile_cache(), enable_compile_cache()
        assert first == second == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_last_line_is_the_result_json(cs, monkeypatch, capsys):
    """With the device check passing (a stand-in TPU device) and the two
    heavy phases stubbed, ``main`` ends on exactly the result object."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(cs, "device_check", lambda: dev)
    monkeypatch.setattr(cs, "check_kernels", lambda *a, **k: None)
    monkeypatch.setattr(cs, "serve_phase", lambda *a, **k: {})
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off")
    cs.main([], plan=cs.Plan(reduced=True))
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": dev}
