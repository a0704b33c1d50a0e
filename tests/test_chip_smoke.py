"""``chip_smoke.py`` on the CPU: its refusals, and its phase functions at a
tiny size with the Pallas kernels in interpret mode.

The script itself only passes on a TPU; here it must exit non-zero and
print no ``ok`` line.  The phases it runs on the chip are exercised with
the same code at the "tiny" preset: the Pallas-vs-XLA epoch parity and
``train_single`` + ``run_protocol`` in this process, and the PAC phase on
four virtual CPU devices in a subprocess (the device count is fixed when
JAX starts).  Also covers where ``launch.cache`` puts the compile cache.
"""

import dataclasses
import importlib.util
import os
import random
import subprocess
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_cfg(smoke, g):
    # TIG's structure (tgn, 2 heads) at widths interpret mode runs quickly
    return dataclasses.replace(smoke.tig_config(g, "interpret"), dim=16,
                               dim_time=8, num_neighbors=4, batch_size=50)


def _run_script(env_extra, timeout=120):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_script_refuses_cpu():
    proc = _run_script({})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_script_refuses_kernel_override():
    for var, val in (("REPRO_KERNEL_BACKEND", "xla"),
                     ("REPRO_KERNEL_BWD", "oracle")):
        proc = _run_script({var: val})
        assert proc.returncode != 0, var
        assert '"ok"' not in proc.stdout
        assert "off Pallas" in proc.stderr


def test_single_chip_phases_tiny_interpret():
    smoke = _load()
    full, prefix = smoke.make_graph("tiny", 0, 1_000)
    part = smoke.partition_phase(full, 4)
    assert part.num_parts == 4
    cfg = _tiny_cfg(smoke, full)
    out = smoke.parity_phase(prefix, cfg, 0)
    lp, lx = out["pallas"]["losses"], out["xla"]["losses"]
    assert len(lp) == len(lx) > 1
    # interpret mode has no Mosaic custom calls; the census is empty
    assert out["pallas"]["tpu_custom_calls"] == 0
    np.testing.assert_allclose(lp, lx, rtol=1e-5)
    np.testing.assert_allclose(out["pallas"]["logits"], out["xla"]["logits"],
                               rtol=1e-5, atol=1e-6)
    metrics = smoke.train_phase(prefix, cfg, 0)
    assert 0.0 <= metrics["val_ap"] <= 1.0


def test_pac_phase_four_virtual_devices():
    script = (
        "import sys; sys.argv = ['chip_smoke']\n"
        "import importlib.util, dataclasses\n"
        f"spec = importlib.util.spec_from_file_location('s', "
        f"{str(REPO / 'chip_smoke.py')!r})\n"
        "s = importlib.util.module_from_spec(spec); "
        "spec.loader.exec_module(s)\n"
        "from repro.launch.mesh import make_tig_mesh\n"
        "_, g = s.make_graph('tiny', 0, 1200)\n"
        "cfg = dataclasses.replace(s.tig_config(g, 'interpret'), dim=16,\n"
        "    dim_time=8, num_neighbors=4, batch_size=50)\n"
        "out = s.pac_phase(g, cfg, 0, make_tig_mesh(4))\n"
        "print('PAC_GAPS', sorted(out['gaps']))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    # lr=0: every step of both epochs; training: the first steps of epoch 0
    assert "pac_mesh_vs_reference_lr0_epoch1" in proc.stdout
    assert "pac_mesh_vs_reference_lr0.001_epoch0" in proc.stdout


def test_compile_cache_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there;
    without it the helper picks the fixed directory in the checkout."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.cache import setup_compile_cache\n"
        "print('CACHE', setup_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()\n"
        "print('CONFIG', jax.config.jax_compilation_cache_dir)\n")
    base = {**os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": str(REPO / "src")}
    base.pop("JAX_COMPILATION_CACHE_DIR", None)
    env = {**base, "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert f"CACHE {tmp_path}" in proc.stdout
    assert any(tmp_path.iterdir()), "no cache entry written"

    from repro.launch.cache import CHECKOUT_CACHE
    assert CHECKOUT_CACHE == REPO / ".jax_cache"
    before = set(CHECKOUT_CACHE.iterdir()) if CHECKOUT_CACHE.exists() else set()
    # a constant no earlier run compiled, so the program is a cache miss
    probe = script.replace("jnp.sin(x)", f"jnp.sin(x) * {random.random()!r}")
    proc = subprocess.run([sys.executable, "-c", probe], env=base,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert f"CACHE {CHECKOUT_CACHE}" in proc.stdout
    assert f"CONFIG {CHECKOUT_CACHE}" in proc.stdout
    assert set(CHECKOUT_CACHE.iterdir()) - before, "no cache entry written"
