"""CPU rehearsal of chip_smoke.py, and the entry points' compile-cache helper.

The smoke test runs only on a TPU; these tests keep it from rotting between
chip runs.  They call its phase functions directly at tiny sizes with the
interpreted Pallas kernel (never `main()` in this process, which would hunt
for a chip), check that the script refuses to run without a TPU or without
the rest of the repo, and rehearse the four-chip path on four simulated CPU
devices in a child process.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

import jax

from repro.core.collectives import host_device_count_env
from repro.launch import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

#: the smoke's phases cut to CPU-interpreter size (same checks, more tiles)
TINY = {
    "wide": ("hapmap_dom_20", 0.1, 100),
    "tiled": ("alz_rec_30", 0.004, 200),
    "lamp": ("hapmap_dom_10", 0.02),
    "service_seeds": (1, 2),
    "item_tile": 512,
}
KERNEL = "pallas_interpret"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def one_device():
    return jax.devices()[:1]


# ----------------------------------------------------------- phase rehearsal
def test_phase_wide_pallas_matches_ref(smoke, one_device):
    ds, rep = smoke.phase_wide(one_device, KERNEL, *TINY["wide"],
                               TINY["item_tile"])
    assert ds.bucket.n_tiles > 1
    assert rep.kernel_impl == KERNEL and len(rep.results) > 0


def test_phase_tiled_pallas_matches_ref(smoke, one_device):
    smoke.phase_tiled(one_device, KERNEL, *TINY["tiled"], TINY["item_tile"])


def test_phase_lamp_matches_oracle(smoke, one_device):
    ds, session = smoke.phase_lamp(one_device, KERNEL, *TINY["lamp"])
    assert session.has_programs(ds.bucket, "fisher")


def test_phase_service_matches_direct_runs(smoke, one_device):
    direct = smoke.make_session(one_device, KERNEL)
    smoke.phase_service(one_device, KERNEL, *TINY["lamp"],
                        TINY["service_seeds"], direct)


def test_phase_checks_catch_a_kernel_fallback(smoke, one_device, monkeypatch):
    """A pass that ran another kernel than the one asked for fails."""
    real = smoke.make_session
    monkeypatch.setattr(smoke, "make_session",
                        lambda devices, kernel: real(devices, "ref"))
    with pytest.raises(smoke.SmokeFailure,
                       match="ran kernel 'ref', not 'pallas_interpret'"):
        smoke.phase_tiled(one_device, KERNEL, *TINY["tiled"], TINY["item_tile"])


def test_four_chip_path_on_simulated_devices():
    """--four-chips' check on 4 simulated CPU devices, in a child process
    (the device count is fixed at the child's first jax init)."""
    code = textwrap.dedent(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        smoke.four_chips(jax.devices(), {KERNEL!r}, {TINY!r})
        print("FOUR_CHIPS_OK")
    """)
    env = host_device_count_env(4)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_CHIPS_OK" in out.stdout
    line = [x for x in out.stdout.splitlines() if "per_device_popped" in x][0]
    assert "steals=0" not in line


# ------------------------------------------------------------ refusing to run
def _run_script(args, *, cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          capture_output=True, text=True, env=env, timeout=300)


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_main_fails_without_a_tpu(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run_script(args, cwd=ROOT, env=env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs" in out.stderr


def test_script_alone_fails(tmp_path):
    """In a directory that holds only chip_smoke.py the script fails."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = _run_script([], cwd=tmp_path, env=env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


# -------------------------------------------------------------- compile cache
def test_compile_cache_env_var_wins(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []  # JAX's own setting is left alone


@pytest.mark.parametrize("backend,want", [
    ("tpu", os.path.join(ROOT, ".jax_cache")),
    ("cpu", None),
], ids=["tpu", "cpu"])
def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch, backend, want):
    """Unset variable: the fixed <repo>/.jax_cache on an accelerator, no
    cache on the CPU."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    assert compile_cache.REPO_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert calls == ([("jax_compilation_cache_dir", want)] if want else [])
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
