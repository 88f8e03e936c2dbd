"""End-to-end smoke: tiny configs through the real drivers on synthetic data,
exercising the full stack (config -> data -> augment -> sharded step -> ckpt ->
probe restore -> validation), all on the virtual 8-device CPU mesh.

Sized for the single-core CPU test host: 16x16 images, a few hundred examples,
a handful of steps — compile time dominates, so keep program count low.
"""

import numpy as np
import pytest

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu.data import cifar as cifar_lib
from simclr_pytorch_distributed_tpu.train import ce as ce_driver
from simclr_pytorch_distributed_tpu.train import linear as linear_driver
from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver

SIZE = 16  # image side for all integration runs


@pytest.fixture(autouse=True)
def small_synthetic(monkeypatch):
    import jax

    from simclr_pytorch_distributed_tpu.parallel import mesh as mesh_lib

    orig = cifar_lib.synthetic_dataset

    def small(n=2048, num_classes=10, seed=0, size=32):
        return orig(n=320, num_classes=num_classes, seed=seed, size=SIZE)

    monkeypatch.setattr(cifar_lib, "synthetic_dataset", small)

    # 1-device mesh: the GSPMD partitioner cost on the 1-core CPU host scales
    # with partition count, and multi-way sharding semantics are covered by
    # test_distributed.py — integration only needs the drivers end-to-end.
    # The drivers import create_mesh by name, so patch their module bindings.
    def limited_create_mesh(devices=None, **kw):
        if devices is None:
            devices = jax.devices()[:1]
        return mesh_lib.create_mesh(devices=devices, **kw)

    for driver in (supcon_driver, linear_driver, ce_driver):
        monkeypatch.setattr(driver, "create_mesh", limited_create_mesh)


def supcon_cfg(tmp_path, **over):
    base = dict(
        model="resnet10", dataset="synthetic", batch_size=64, epochs=2,
        learning_rate=0.05, temp=0.5, cosine=True, syncBN=True,
        save_freq=2, print_freq=2, size=SIZE, workdir=str(tmp_path),
        seed=0, method="SimCLR",
    )
    base.update(over)
    cfg = config_lib.SupConConfig(**base)
    return config_lib.finalize_supcon(cfg)


def test_supcon_then_probe_end_to_end(tmp_path):
    cfg = supcon_cfg(tmp_path)
    state = supcon_driver.run(cfg)
    # synthetic: 320 - 40 test = 280 train -> 4 steps/epoch at batch 64
    assert int(state.step) == 2 * (280 // 64)

    lcfg = config_lib.LinearConfig(
        model="resnet10", dataset="synthetic", batch_size=64, epochs=2,
        learning_rate=0.5, size=SIZE, val_batch_size=40, workdir=str(tmp_path),
        ckpt=f"{cfg.save_folder}/last", print_freq=2,
    )
    lcfg = config_lib.finalize_linear(lcfg)
    best_acc, best_acc5 = linear_driver.run(lcfg)
    # synthetic data is class-conditional color: even 2 epochs beats chance (10%)
    assert best_acc > 15.0, best_acc
    assert best_acc5 >= best_acc


def test_supcon_resume(tmp_path):
    cfg = supcon_cfg(tmp_path, epochs=1, save_freq=1)
    state1 = supcon_driver.run(cfg)
    cfg2 = supcon_cfg(tmp_path, epochs=2, resume=f"{cfg.save_folder}/last")
    state2 = supcon_driver.run(cfg2)
    assert int(state2.step) == 2 * int(state1.step)


def test_ce_driver_end_to_end(tmp_path):
    # lr 0.1: lr=0.5 was on the edge of divergence for a from-scratch CNN on
    # 280 samples — tiny numeric perturbations flipped the trajectory between
    # ~8% and ~20% val top-1. At lr 0.1 / 6 epochs the margin over the 30%
    # bar is wide (72.5% observed on rn10 with this exact seed/config; 10
    # epochs reached 60-82% on rn18 — trimmed to keep `pytest -m slow` inside
    # a 10-minute harness budget).
    cfg = config_lib.LinearConfig(
        model="resnet10", dataset="synthetic", batch_size=64, epochs=6,
        learning_rate=0.1, size=SIZE, val_batch_size=40, workdir=str(tmp_path),
        print_freq=100,
    )
    cfg = config_lib.finalize_linear(cfg, prefix="ce_")
    best_acc, best_acc5 = ce_driver.run(cfg)
    assert best_acc > 30.0, (best_acc, best_acc5)
    assert best_acc5 >= best_acc
