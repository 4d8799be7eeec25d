"""chip_smoke.py's phases on the CPU: paths, control flow and checks at
small sizes, with the Pallas kernels in interpret mode.  What only the
chip can show (compiled kernels, device memory, times) is left to
``python chip_smoke.py`` on a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

SMALL_KERNEL_SHAPES = {
    "flash_attention": {"model": "small", "b": 1, "hq": 4, "hkv": 2,
                        "s": 256, "d": 64},
    "ssd": {"model": "small", "b": 1, "s": 256, "h": 4, "p": 64, "g": 2,
            "n": 128, "chunk": 128},
    "rmsnorm": {"model": "small", "rows": 256, "d": 256},
    "moe_gmm": {"model": "small", "t": 512, "e": 2, "k": 256, "n": 256,
                "block_t": 128},
}


def _env(**kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + ([env["PYTHONPATH"]]
                               if env.get("PYTHONPATH") else []))
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_KERNEL_BACKEND", None)
    for k, v in kw.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


def test_study_phase_jax_matches_numpy(capsys):
    out = cs.phase_study()
    assert out["step_err"] <= cs.STEP_RTOL
    assert out["frontier"]
    text = capsys.readouterr().out
    assert "run=jax_cold" in text and "check=jax_vs_numpy" in text


def test_kernel_phase_interpret_matches_reference():
    errs = cs.phase_kernels(SMALL_KERNEL_SHAPES, interpret=True)
    assert set(errs) == {"flash_attention_fwd", "flash_attention_bwd",
                         "ssd", "rmsnorm", "moe_gmm"}
    assert max(errs.values()) < 1e-4


def test_kernel_phase_rejects_a_wrong_kernel(monkeypatch):
    from repro.kernels import rmsnorm as rn_mod
    real = rn_mod.rmsnorm
    monkeypatch.setattr(rn_mod, "rmsnorm",
                        lambda x, w, **kw: real(x, w, **kw) * 1.1)
    with pytest.raises(cs.SmokeError, match="rmsnorm"):
        cs.phase_kernels(SMALL_KERNEL_SHAPES, interpret=True)


def test_train_phase_reduced(tmp_path, capsys):
    out = cs.phase_train(n_layers=2, reduced=True, batch=2, seq=64,
                         out_dir=tmp_path, expect_kernels=False)
    assert len(out["losses"]) == cs.TRAIN_STEPS
    assert out["losses"][0] == pytest.approx(out["ref_loss"], rel=1e-6)
    assert not out["pallas_in_step"]          # xla kernels on the CPU
    assert "layers=2/22" in capsys.readouterr().out


def test_train_phase_wants_pallas_in_the_step(tmp_path):
    with pytest.raises(cs.SmokeError, match="tpu_custom_call"):
        cs.phase_train(n_layers=2, reduced=True, batch=2, seq=64,
                       steps=1, out_dir=tmp_path)


def test_sharded_train_phase_on_four_cpu_devices(tmp_path):
    code = ("import importlib.util, sys\n"
            f"s = importlib.util.spec_from_file_location('cs', "
            f"{str(ROOT / 'chip_smoke.py')!r})\n"
            "cs = importlib.util.module_from_spec(s)\n"
            "s.loader.exec_module(cs)\n"
            "out = cs.phase_train_sharded(n_layers=2, reduced=True, "
            f"batch=2, seq=32, out_dir={str(tmp_path)!r})\n"
            "print(sorted(out['param_bytes']), out['collectives'])\n")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=tmp_path,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "[train4]" in r.stdout and "[0, 1, 2, 3]" in r.stdout


def test_main_refuses_without_a_tpu(capsys):
    assert cs.main([]) != 0
    cap = capsys.readouterr()
    assert '"ok"' not in cap.out
    assert "no TPU" in cap.err


def test_main_refuses_a_kernel_backend_override(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env=_env(PYTHONPATH=None))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_follows_the_environment(tmp_path):
    code = ("import jax\n"
            "from repro.runtime.compile_cache import use_compile_cache\n"
            "d = use_compile_cache()\n"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n"
            "print(d, jax.config.jax_compilation_cache_dir)\n")
    cache = tmp_path / "cache"
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=tmp_path,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                 JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())

    code = ("import jax\n"
            "from repro.runtime.compile_cache import use_compile_cache\n"
            "print(use_compile_cache(), jax.config.jax_compilation_cache_dir)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=tmp_path,
                       env=_env(JAX_COMPILATION_CACHE_DIR=None))
    assert r.returncode == 0, r.stderr[-3000:]
    repo_cache = str(ROOT / ".jax_cache")
    assert r.stdout.split() == [repo_cache, repo_cache]


def test_last_line_is_the_ok_object(monkeypatch, capsys):
    """On a TPU the script's last line is exactly the ok object; here the
    device check and the phases are stood in for."""
    from repro.runtime import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "-")
    monkeypatch.setattr(cs, "device_info", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    for name in ("phase_study", "phase_kernels", "phase_train"):
        monkeypatch.setattr(cs, name, lambda *a, **k: None)
    assert cs.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
