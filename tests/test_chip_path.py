"""The chip entry point and the one-process-per-chip rules, on the CPU.

``chip_smoke.py`` refuses to run without a TPU, so its phases are driven
here directly at small sizes: the same checks (oracle-exact masks, cache
hits, no retrace, brute-force cluster labels) against small graphs.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from repro.core.distributed_mst import make_flat_mesh
from repro.graphs.generator import generate_graph, generate_points

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "phase" not in proc.stdout
    assert '"ok"' not in proc.stdout


def _clock():
    compiles = chip_smoke.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    return chip_smoke.PhaseClock(compiles, jax.devices()[:1]), compiles


def test_chip_smoke_phases_pass_at_small_size(capsys):
    clock, compiles = _clock()
    try:
        chip_smoke.run_one_chip(
            clock, compiles, generate_graph(3000, 6, seed=0),
            generate_graph(3000, 6, seed=1),
            [generate_graph(n, 6, seed=s) for n in (300, 40)
             for s in range(2)],
            generate_graph(300, 6, seed=0),
            generate_points("blobs", 200, 2, seed=0, num_blobs=3), 3)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    out = capsys.readouterr().out
    for phase in ("a_solver", "b_service", "c_spmm", "d_dynamic",
                  "e_cluster"):
        assert f"phase {phase}:" in out
    assert "spmm candidate path on cpu: jnp segment_min" in out


def test_chip_smoke_mesh_phase_on_one_device_mesh():
    clock, compiles = _clock()
    g = generate_graph(500, 5, seed=3)
    try:
        got = clock.run("mesh", chip_smoke.phase_mesh, g,
                        chip_smoke.oracle_mask(g), make_flat_mesh(1))
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    assert got["sharded/cas"] == got["single/cas"]
    assert got["distributed/lock"] == got["single/lock"]


def test_expect_mask_rejects_one_flipped_edge():
    g = generate_graph(200, 4, seed=5)
    oracle = chip_smoke.oracle_mask(g)
    wrong = oracle.copy()
    wrong[np.flatnonzero(oracle)[0]] = False
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.expect_mask(wrong, oracle, "flipped")


def test_make_flat_mesh_raises_when_devices_are_short():
    with pytest.raises(ValueError, match="devices exist"):
        make_flat_mesh(len(jax.devices()) + 1)


def _mesh_size_point(n, scale):
    return {"devices": n, "value": n * scale,
            "mesh": len(make_flat_mesh(n).devices)}


def test_mesh_sizes_run_in_process_on_tpu(monkeypatch):
    """On a TPU backend no child process is started (it could not reach
    the chip this process holds), and sizes above the device count are
    skipped instead of silently shrinking the mesh."""
    from benchmarks import mst_figures

    def no_child(*a, **k):
        raise AssertionError("started a child process on a TPU backend")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mst_figures.subprocess, "run", no_child)
    n_dev = len(jax.devices())
    got = mst_figures.run_mesh_sizes(_mesh_size_point, (1, n_dev + 1),
                                     scale=3)
    assert got == [{"devices": 1, "value": 3, "mesh": 1}]


def test_mesh_sizes_use_a_cpu_child_off_tpu(monkeypatch):
    """Off-TPU the sizes run in one CPU-only child with forced host
    devices, which the child's environment pins."""
    from benchmarks import mst_figures

    seen = {}

    def fake_child(cmd, env, **kw):
        seen.update(env=env, sizes=json.loads(cmd[-1]))
        return subprocess.CompletedProcess(
            cmd, 0, stdout='RESULT:[{"devices": 4}]\n', stderr="")

    monkeypatch.setattr(mst_figures.subprocess, "run", fake_child)
    got = mst_figures.run_mesh_sizes(_mesh_size_point, (2, 4), scale=1)
    assert got == [{"devices": 4}]
    assert seen["sizes"] == [2, 4]
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in \
        seen["env"]["XLA_FLAGS"]
