"""The harness itself: it refuses a machine without a TPU; every cell walks
its whole control flow at tiny size on the CPU (and once as a cell of four
chips, on four forced host devices); and with the timed path broken
underneath, or the program's lower precision switched on, ``correct`` comes
out false.

The broken runs skip the harness's look for a chip (``run.drive`` is what
``run.main`` calls after it) and drive everything else: set-up through
``train_one_epoch``, the window, the readings, the reference, the comparison
under the cell's committed limits.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import run as harness

CELLS = ["rn50-cifar.pretrain-b256", "rn18-cifar.pretrain-b1024"]
ONE_CHIP = "rn18-cifar.pretrain-b1024"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rehearse(cell, seed, **kw):
    return harness.drive(cell, seed, 1.0, False, rehearse=True, **kw)


def test_refuses_a_machine_without_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", ONE_CHIP, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_unknown_cell_fails_before_jax():
    with pytest.raises(FileNotFoundError):
        harness.main(["--workload", "no-such-cell", "--seed", "1", "--seconds", "1"])


def sound(res, chips=1):
    assert res["metrics"] == {} and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == chips
    assert list(res)[-1] == "compared"
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device", "compared"}
    limits = harness.load_cell(ONE_CHIP)["limits"]
    assert set(res["compared"]) == set(limits) | {"compiled_in_window"}
    assert res["compared"]["compiled_in_window"] == {"value": 0, "limit": 0}
    assert res["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_walks_the_cell(cell):
    sound(rehearse(cell, seed=2147483650))


@pytest.fixture
def four_chips(monkeypatch):
    """The one-chip cell as a cell of four chips: the harness's mesh, store,
    state and reference spread over ``data=4``, for the cell that a later PR
    adds (PERF.md, Open questions)."""
    real = harness.load_cell
    monkeypatch.setattr(harness, "load_cell", lambda name: dict(real(name), chips=4))


def test_rehearsal_on_four_host_devices(four_chips):
    sound(rehearse(ONE_CHIP, seed=2147483651), chips=4)


def _break_update(monkeypatch, wrap):
    from simclr_pytorch_distributed_tpu.train import supcon

    real_make = supcon.make_fused_update
    monkeypatch.setattr(supcon, "make_fused_update",
                        lambda *a, **k: wrap(real_make(*a, **k)))


def test_fault_state_left_unchanged(monkeypatch):
    def wrap(real):
        def update(state, ring, images, labels, key):
            before = jax.tree.map(jnp.copy, state)
            after, ring = real(state, ring, images, labels, key)
            return before.replace(step=after.step), ring
        return update

    _break_update(monkeypatch, wrap)
    res = rehearse(ONE_CHIP, seed=11)
    assert res["correct"] is False
    assert res["compared"]["change_median_gap"]["value"] > 0.9  # reads about 1


def test_fault_half_of_the_batch_left_out(monkeypatch):
    from simclr_pytorch_distributed_tpu.train import supcon

    real_views = supcon.two_crop_batch
    monkeypatch.setattr(supcon, "two_crop_batch",
                        lambda key, images, cfg: real_views(key, images, cfg)[: images.shape[0] // 2])
    res = rehearse(ONE_CHIP, seed=12)
    assert res["correct"] is False
    assert {"loss1_gap", "grad_median_gap", "change_median_gap"} <= set(over_a_limit(res))


def over_a_limit(res):
    return [k for k, row in res["compared"].items() if not row["value"] <= row["limit"]]


def test_fault_exchange_between_chips_left_out(monkeypatch, four_chips):
    """What one of four chips computes when nothing is exchanged: the loss over
    its own rows alone (no all-gather) and its own gradient (no mean)."""
    from simclr_pytorch_distributed_tpu.train import supcon

    real_views = supcon.two_crop_batch
    monkeypatch.setattr(supcon, "two_crop_batch",
                        lambda key, images, cfg: real_views(key, images, cfg)[: images.shape[0] // 4])
    res = rehearse(ONE_CHIP, seed=13)
    assert res["correct"] is False and over_a_limit(res)


def test_control_lower_precision_is_not_correct():
    """The control: the program with its own lower precision switched on
    (``--bf16``), through the whole of a run. PERF.md has its readings on the
    chip at each cell's size."""
    res = rehearse(ONE_CHIP, seed=14, flag_overrides=["--bf16"])
    assert res["correct"] is False and over_a_limit(res)


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]] == [c for c in CELLS if c in
                                                       [w["name"] for w in bench["workloads"]]]
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        assert {"loss1_gap", "grad_median_gap", "change_median_gap"} <= set(cell["limits"])
        for key in ("reference", "adapter", "flops"):
            assert callable(harness.load_module(cell["config_file"][key]).__dict__.get(
                {"reference": "trajectory", "adapter": "to_reference", "flops": "step_flops"}[key]))
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            stated = json.load(f)
        assert stated["reduced"] == c["reduced"] == []
        assert stated["source"].startswith(c["source"])
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for name in names:
        assert callable(harness.load_reader(name).read)
    on_disk = {f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmark", "metrics"))
               if f.endswith(".py")}
    assert on_disk == set(names)
    for m in bench["per_layer"]:
        assert m["moves"] == "pretrain_imgs_per_s"
        doc = harness.load_reader(m["name"]).__doc__
        assert f'layer "{m["layer"]}"' in doc and m["moves"] in doc


def test_peaks_have_no_default():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v99")
