"""Entry-point plumbing that a chip run relies on: the compile cache's
placement, the device-keyed peaks table, the (data, model) host mesh, the
serve CLI's size switch, and the build-time refusal of fused training on a
TPU."""
import os

import jax
import pytest

from repro.configs import get_config
from repro.configs.hyca_dla import dla_config
from repro.launch import hw, train
from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache
from repro.launch.mesh import make_host_mesh

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_sets_nothing_else(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_gitignored_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == CACHE_DIR == jax.config.jax_compilation_cache_dir
    assert os.path.dirname(CACHE_DIR) == REPO
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert os.path.basename(CACHE_DIR) + "/" in f.read().split()


def test_peaks_keyed_by_device_kind():
    v5e = hw.peaks("TPU v5 lite")
    # ici_bw is per link, the unit of the roofline's per-device ring bytes
    assert (v5e.bf16_flops, v5e.hbm_bw, v5e.ici_bw) == (197e12, 819e9, 50e9)
    with pytest.raises(ValueError, match="no published peaks"):
        hw.peaks("cpu")


def test_host_mesh_axes_are_auto():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)


def test_fused_training_refused_at_build_under_pallas(monkeypatch):
    monkeypatch.setattr(train, "fused_backend", lambda: "pallas")
    tc = train.TrainConfig(hyca_mode="protected", hyca_dispatch="fused")
    with pytest.raises(ValueError, match="cannot train on a TPU"):
        train.make_train_step(get_config("qwen1.5-0.5b"), tc, make_host_mesh(), None, None,
                              hyca=dla_config())


def test_serve_cli_smoke_switch(monkeypatch, tmp_path):
    from repro.launch import serve

    # set: the CLI's cache helper leaves this process's JAX config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    summary = serve.main(["--smoke", "--requests", "2", "--prompt-len", "3", "--gen", "2",
                          "--slots", "2"])
    assert summary["requests_completed"] == 2
