"""chip_smoke.py's phases, rehearsed on the CPU at reduced sizes, and the
process rules around the chip: the platform picks the kernel mode, the
smoke script refuses a host without a TPU, the dry-run claims the CPU, and
the compile cache sits at a fixed place."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import ARCHS, reduced
from repro.kernels import resolve_interpret

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
CFG = reduced(ARCHS["olmo-1b"]).replace(remat=True)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


def _run(args, *, env_drop=(), env_set=None, cwd=ROOT, timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(env_set or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=timeout)


# -- the phases at reduced sizes ---------------------------------------------


def test_phase_train(smoke):
    losses = smoke.phase_train(CFG, steps=3, batch=2, seq=32)
    assert len(losses) == 3


def test_phase_serve(smoke):
    ratio = smoke.phase_serve(CFG, n_requests=2, prompt_len=16, gen=5)
    assert ratio <= smoke.SERVE_TOL


def test_phase_aggregate(smoke):
    smoke.phase_aggregate(n_workers=8, shard_len=8192 * 2)
    smoke.drop_arrays("aggregate")


def test_four_chip_phase_on_four_cpu_devices():
    """The --four-chips phase (and the launcher's sharded batches) on four
    virtual CPU devices: wrong meshes and sharding rules show up here."""
    code = textwrap.dedent(f"""
        import importlib.util, jax
        from repro.configs import ARCHS, reduced
        from repro.launch.train import train
        assert len(jax.devices()) == 4, jax.devices()
        spec = importlib.util.spec_from_file_location("s", {SMOKE!r})
        s = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(s)
        cfg = reduced(ARCHS["olmo-1b"]).replace(remat=True)
        _, losses, _ = train(cfg, steps=2, batch=4, seq=32, strategy="hier")
        s.phase_four_chips(cfg, jax.devices(), batch=4, seq=32, big_seq=64)
        print("OK four_chips")
    """)
    out = _run(["-c", code], env_set={
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "OK four_chips" in out.stdout
    assert "(device, shard) [(0, " in out.stdout


# -- process rules ------------------------------------------------------------


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_smoke_refuses_without_tpu(tmp_path, alone):
    """No TPU (or no repository beside the script): non-zero, no result."""
    script = SMOKE
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    out = _run([script], env_set={"JAX_PLATFORMS": "cpu",
                                  "PYTHONPATH": ""}, cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_interpret_mode_follows_platform(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert resolve_interpret(False) is False      # tests force a compile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        resolve_interpret()


def test_dryrun_claims_cpu():
    """Even with no platform in the environment, the dry-run (and every
    process it is started from) takes the CPU, never an attached chip."""
    out = _run(["-c", "import os, repro.launch.dryrun, jax; "
                "print(os.environ['JAX_PLATFORMS'], jax.default_backend(), "
                "jax.device_count())"], env_drop=("JAX_PLATFORMS",))
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == ["cpu", "cpu", "512"]


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(tmp_path, env_dir):
    env_set = {"JAX_PLATFORMS": "cpu"}
    if env_dir:
        env_set["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = _run(["-c", "import jax; from repro.launch.compile_cache import "
                "enable_compile_cache as e; print(e()); "
                "print(jax.config.jax_compilation_cache_dir)"],
               env_drop=("JAX_COMPILATION_CACHE_DIR",), env_set=env_set)
    assert out.returncode == 0, out.stderr[-3000:]
    helper, config = out.stdout.split()
    want = str(tmp_path / env_dir) if env_dir else os.path.join(
        ROOT, ".jax_cache")
    assert helper == want
    # with the variable set, JAX reads it itself and no code overrides it
    assert config == want
