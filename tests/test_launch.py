"""Entry points: the serve and train launchers fail loudly, the compile
cache lives where it is told, peak rates are keyed by device kind, and the
dry-run leaves importers' XLA flags alone."""

from __future__ import annotations

import importlib
import math
import os
import sys

import jax
import numpy as np
import pytest

from repro.launch import compile_cache, roofline
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


@pytest.fixture
def no_cache(monkeypatch):
    """Launcher mains would point JAX's cache into the checkout; not here."""
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")


def test_serve_results_identical_to_solo_pushdown_off(tmp_path):
    report = serve_mod.serve(str(tmp_path / "lake"), sf=0.01, requests=15,
                             fresh=True, verify=True, log=lambda m: None)
    assert report.ok
    assert report.n_ok == report.n_requests == 15
    assert report.latency["count"] == 15
    assert {name for name, _ in serve_mod.make_requests(15)} <= set(
        serve_mod.BI_GSQL)


def test_serve_main_exits_nonzero_on_failed_request(tmp_path, monkeypatch,
                                                    no_cache):
    # a request missing its bound parameter fails inside the server
    monkeypatch.setattr(serve_mod, "make_requests",
                        lambda n, seed=0: [("bi3", {"min_len": 500}),
                                           ("bi4", {})])
    monkeypatch.setattr(sys, "argv", ["serve", "--root", str(tmp_path / "lake"),
                                      "--sf", "0.01", "--fresh"])
    with pytest.raises(SystemExit) as exc:
        serve_mod.main()
    assert exc.value.code == 1


def test_train_launcher_places_state_with_mesh_shardings():
    run = train_mod.train("gin-tu", "minibatch_lg", steps=2, reduced=True,
                          mesh_devices=True)
    assert run.result.steps_run == 2 and not run.failed_steps
    assert run.mesh is not None and run.batch_shardings is not None
    for leaf in jax.tree.leaves(run.result.final_state):
        assert isinstance(leaf.sharding, jax.sharding.NamedSharding)


def test_train_main_exits_nonzero_on_nonfinite_loss(monkeypatch, no_cache):
    real_train = train_mod.train

    def nan_train(*args, **kwargs):
        run = real_train(*args, **kwargs)
        run.result.losses[-1] = math.nan
        return run

    monkeypatch.setattr(train_mod, "train", nan_train)
    monkeypatch.setattr(sys, "argv", ["train", "--arch", "gin-tu", "--reduced",
                                      "--steps", "2"])
    with pytest.raises(SystemExit) as exc:
        train_mod.main()
    assert exc.value.code == 1


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(compile_cache.CHECKOUT_ROOT, ".jax_cache")
        assert os.path.isfile(os.path.join(compile_cache.CHECKOUT_ROOT,
                                           "chip_smoke.py"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peaks_keyed_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")
    terms = roofline.derive_terms("a", "c", "m", 4, {"flops": 4 * 197e12},
                                  "", model_flops=0.0, device_kind="TPU v5 lite")
    np.testing.assert_allclose(terms.compute_s, 1.0)
    with pytest.raises(KeyError):
        roofline.derive_terms("a", "c", "m", 1, {}, "", model_flops=0.0,
                              device_kind="unknown chip")


def test_dryrun_import_keeps_caller_xla_flags(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/nonexistent")
    dryrun = importlib.import_module("repro.launch.dryrun")
    importlib.reload(dryrun)
    assert os.environ["XLA_FLAGS"] == "--xla_dump_to=/nonexistent"
    assert dryrun.peaks(dryrun.MODELED_KIND).hbm_bytes == 16e9
