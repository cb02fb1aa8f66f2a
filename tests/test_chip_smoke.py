"""chip_smoke.py: it refuses to run without a TPU or without the repo, and
its phases hold their own checks at a reduced size on the CPU."""
import os
import shutil
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _env(**kw):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu", **kw)
    return env


def test_chip_smoke_fails_without_tpu():
    r = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, env=_env(), cwd=ROOT, timeout=300)
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_alone(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, env=_env(),
                       cwd=tmp_path, timeout=300)
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_phases_on_cpu_at_reduced_size():
    """Every phase at deepseek-7b's reduced widths on 4 host devices, with
    the Pallas kernel in interpret mode: the script's own comparisons must
    hold (the chip run is the real size)."""
    code = textwrap.dedent("""
        import dataclasses, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, %r)
        import numpy as np, jax
        from jax.sharding import Mesh
        import chip_smoke as cs
        from repro.configs import get_config
        cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                                  dtype="bfloat16")
        cs.train_phase(dataclasses.replace(cfg, n_layers=2), workers=2,
                       batch=2, seq=32, steps=2, p=0.1)
        cs.serve_phase(dataclasses.replace(cfg, n_layers=2), page=16,
                       kv_blocks=33, max_batch=4, chunk=8, requests=4,
                       prompt_lens=(12, 24), max_new=(8, 16), check_steps=4)
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        cs.mesh_phase(dataclasses.replace(cfg, n_layers=2), mesh, batch=2,
                      seq=32)
        cs.ring_phase(mesh, widths={"float32": 1024, "bfloat16": 512})
        assert not cs.FAILED, cs.FAILED
        print("PHASES_OK")
    """) % ROOT
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_env(), cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PHASES_OK" in r.stdout
    for line in ("train exchange check", "serve logit check",
                 "mesh p=0 check", "mesh p=0.1 check", "ring vs xla"):
        assert line in r.stdout, r.stdout
