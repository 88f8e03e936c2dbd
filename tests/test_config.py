"""Config parity tests: flag names/defaults and the derivations that matter
(model_name encoding, auto-warm, closed-form warmup_to), plus a MECHANICAL
pin of the full flag surface against the reference's own argparse."""

import argparse
import ast
import math
import os

import pytest

from simclr_pytorch_distributed_tpu.config import (
    config_dict,
    impl_resolution_banner,
    linear_parser,
    parse_linear,
    parse_supcon,
    supcon_parser,
)

REFERENCE_DIR = "/root/reference"


def test_supcon_defaults_match_reference(tmp_path):
    cfg = parse_supcon(["--workdir", str(tmp_path)])
    assert cfg.print_freq == 10 and cfg.save_freq == 20
    assert cfg.batch_size == 256 and cfg.epochs == 1000
    assert cfg.learning_rate == 0.5 and cfg.lr_decay_epochs == (700, 800, 900)
    assert cfg.lr_decay_rate == 0.1 and cfg.weight_decay == 1e-4
    assert cfg.model == "resnet50" and cfg.dataset == "cifar10"
    assert cfg.method == "SimCLR" and cfg.temp == 0.5
    assert cfg.norm_momentum == 1.0 and cfg.ngpu == 2
    assert cfg.data_folder == "./datasets/"


def test_model_name_encoding(tmp_path):
    cfg = parse_supcon(
        ["--cosine", "--method", "SimCLR", "--trial", "3", "--workdir", str(tmp_path)]
    )
    assert cfg.model_name == (
        "SimCLR_cifar10_resnet50_lr_0.5_decay_0.0001_bsz_256_temp_0.5_trial_3_cosine"
    )
    assert "cifar10_models" in cfg.save_folder
    assert cfg.model_name in cfg.save_folder


def test_auto_warm_large_batch(tmp_path):
    cfg = parse_supcon(
        ["--batch_size", "512", "--cosine", "--epochs", "200", "--workdir", str(tmp_path)]
    )
    assert cfg.warm  # bs > 256 forces warmup (main_supcon.py:120-121)
    assert cfg.warm_epochs == 10 and cfg.warmup_from == 0.01
    eta_min = 0.5 * 0.1**3
    want = eta_min + (0.5 - eta_min) * (1 + math.cos(math.pi * 10 / 200)) / 2
    assert abs(cfg.warmup_to - want) < 1e-9
    assert cfg.model_name.endswith("_warm")


def test_linear_defaults(tmp_path):
    cfg = parse_linear(["--workdir", str(tmp_path)])
    assert cfg.batch_size == 512 and cfg.epochs == 100
    assert cfg.learning_rate == 0.1 and cfg.lr_decay_epochs == (60, 75, 90)
    assert cfg.lr_decay_rate == 0.2 and cfg.weight_decay == 0.0
    assert cfg.n_cls == 10
    cfg100 = parse_linear(["--dataset", "cifar100", "--workdir", str(tmp_path)])
    assert cfg100.n_cls == 100


def test_config_dict_json_safe(tmp_path):
    import json

    cfg = parse_supcon(["--workdir", str(tmp_path)])
    json.dumps(config_dict(cfg))  # must not raise


def test_download_flag(tmp_path):
    """--no_download flips the (default-on) CIFAR fetch fallback; both
    parsers carry it (torchvision download=True parity, main_supcon.py:181)."""
    assert parse_supcon(["--workdir", str(tmp_path)]).download
    assert not parse_supcon(
        ["--no_download", "--workdir", str(tmp_path)]
    ).download
    assert parse_linear(["--workdir", str(tmp_path)]).download
    assert not parse_linear(
        ["--no_download", "--workdir", str(tmp_path)]
    ).download


def _reference_parser(rel_path: str) -> argparse.ArgumentParser:
    """The reference's LIVE ArgumentParser, built by executing the
    parser-construction prefix of its ``parse_option`` (everything before
    ``opt = parser.parse_args()``), extracted via ast. The module itself is
    not importable here (torchvision/tensorboard_logger are absent), but the
    prefix is pure argparse — so the enumeration below reads the reference's
    actual registered actions, not a hand-maintained list."""
    with open(os.path.join(REFERENCE_DIR, rel_path)) as f:
        tree = ast.parse(f.read())
    fn = next(
        n for n in tree.body
        if isinstance(n, ast.FunctionDef) and n.name == "parse_option"
    )
    body = []
    for stmt in fn.body:
        if (
            isinstance(stmt, ast.Assign)
            and isinstance(stmt.value, ast.Call)
            and isinstance(stmt.value.func, ast.Attribute)
            and stmt.value.func.attr == "parse_args"
        ):
            break
        body.append(stmt)
    module = ast.Module(body=body, type_ignores=[])
    ast.fix_missing_locations(module)
    ns = {"argparse": argparse}
    exec(compile(module, rel_path, "exec"), ns)  # noqa: S102 — test oracle
    return ns["parser"]


def _actions_by_flag(parser: argparse.ArgumentParser) -> dict:
    return {
        a.option_strings[0].lstrip("-"): a
        for a in parser._actions
        if a.option_strings and a.option_strings[0] not in ("-h", "--help")
    }


# flags the reference carries that this framework deliberately does not,
# with the reason (the ONLY permitted deltas):
SUPCON_FLAG_DELTAS = {
    # torch.distributed launcher plumbing: process identity comes from
    # jax.distributed (parallel/mesh.py), not a per-process CLI flag
    "local_rank",
}
LINEAR_FLAG_DELTAS: set = set()
# flags whose TYPE is a documented superset of the reference's (the parsed
# value for every reference-legal input must still match):
SUPCON_TYPE_DELTAS = {
    # reference type=int; ours also accepts 'auto' (mesh-resolved grad_div,
    # config.ngpu_arg) — integer inputs parse identically (asserted below)
    "ngpu",
}
LINEAR_TYPE_DELTAS: set = set()


@pytest.mark.skipif(
    not os.path.isdir(REFERENCE_DIR), reason="reference checkout not present"
)
@pytest.mark.parametrize(
    "rel_path,ours,deltas,type_deltas,min_flags",
    [
        ("main_supcon.py", supcon_parser, SUPCON_FLAG_DELTAS,
         SUPCON_TYPE_DELTAS, 30),
        ("main_linear.py", lambda: linear_parser(ce=False), LINEAR_FLAG_DELTAS,
         LINEAR_TYPE_DELTAS, 15),
    ],
)
def test_flag_surface_covers_reference(rel_path, ours, deltas, type_deltas, min_flags):
    """EVERY flag the reference's argparse registers exists here with the
    same default (and at least the same choices), modulo the documented
    deltas — so a round-N edit cannot silently drift the schema."""
    ref_flags = _actions_by_flag(_reference_parser(rel_path))
    # extraction sanity: the ast surgery actually saw the full surface
    assert len(ref_flags) >= min_flags, sorted(ref_flags)
    our_flags = _actions_by_flag(ours())

    missing = [f for f in ref_flags if f not in our_flags and f not in deltas]
    assert not missing, f"{rel_path} flags absent here: {missing}"

    for name, ref in ref_flags.items():
        if name in deltas:
            continue
        mine = our_flags[name]
        assert mine.default == ref.default, (
            f"--{name}: default {mine.default!r} != reference {ref.default!r}"
        )
        if ref.choices:
            assert set(ref.choices) <= set(mine.choices or ()), (
                f"--{name}: choices {mine.choices!r} miss {ref.choices!r}"
            )
        if isinstance(ref, argparse._StoreTrueAction):
            assert isinstance(mine, argparse._StoreTrueAction), f"--{name}"
        elif ref.type is not None:
            if name in type_deltas:
                # documented superset: reference-legal inputs parse the same
                assert mine.type(str(ref.type("3"))) == 3, f"--{name}"
            else:
                assert mine.type is ref.type, (
                    f"--{name}: type {mine.type} != reference {ref.type}"
                )


def test_ce_syncbn_flag(tmp_path):
    """--syncBN exists on the CE parser only (the probe's encoder is frozen
    eval-mode; the reference pretrain conditional, main_supcon.py:223-224)."""
    import pytest

    ce = parse_linear(["--syncBN", "--workdir", str(tmp_path)], ce=True)
    assert ce.syncBN
    assert not parse_linear([
        "--workdir", str(tmp_path)], ce=True).syncBN
    with pytest.raises(SystemExit):
        parse_linear(["--syncBN", "--workdir", str(tmp_path)], ce=False)


def test_ngpu_auto_resolves_to_data_parallel(tmp_path):
    """--ngpu auto -> the mesh's data-parallel size at build time; explicit
    integers pass through (incl. int-like strings from restored configs)."""
    from simclr_pytorch_distributed_tpu.config import ngpu_arg, resolve_ngpu

    cfg = parse_supcon(["--ngpu", "auto", "--workdir", str(tmp_path)])
    assert cfg.ngpu == "auto"
    assert resolve_ngpu(cfg.ngpu, data_parallel=8) == 8
    assert resolve_ngpu(cfg.ngpu, data_parallel=1) == 1
    assert resolve_ngpu(2, data_parallel=8) == 2
    assert resolve_ngpu("4", data_parallel=8) == 4  # restored config dict
    assert ngpu_arg("AUTO") == "auto" and ngpu_arg("2") == 2
    with pytest.raises(argparse.ArgumentTypeError):
        ngpu_arg("two")
    # it becomes the gradient divisor: 0/negative must die at parse, not
    # as a ZeroDivisionError mid-startup (or a sign-flipped update)
    for bad in ("0", "-2"):
        with pytest.raises(argparse.ArgumentTypeError, match="positive"):
            ngpu_arg(bad)
    with pytest.raises(ValueError, match="positive"):
        resolve_ngpu(0, data_parallel=4)
    import json

    json.dumps(config_dict(cfg))  # 'auto' stays JSON-safe in checkpoint meta


def test_ngpu_auto_and_banner_in_build(tmp_path, caplog):
    """build() with --ngpu auto emits NO banner; an explicit mismatch emits
    the startup banner naming the effective-LR consequence."""
    import logging

    from simclr_pytorch_distributed_tpu.config import ngpu_mismatch_banner
    from simclr_pytorch_distributed_tpu.train.supcon import build

    auto_cfg = parse_supcon(
        ["--ngpu", "auto", "--model", "resnet10", "--dataset", "synthetic",
         "--workdir", str(tmp_path)]
    )
    with caplog.at_level(logging.WARNING):
        _, _, _, _, step_cfg = build(auto_cfg, steps_per_epoch=10, n_devices=4)
    assert step_cfg.grad_div == 4.0  # mesh-resolved
    assert "--ngpu" not in caplog.text

    caplog.clear()
    mism_cfg = parse_supcon(
        ["--ngpu", "2", "--model", "resnet10", "--dataset", "synthetic",
         "--workdir", str(tmp_path)]
    )
    with caplog.at_level(logging.WARNING):
        _, _, _, _, step_cfg = build(mism_cfg, steps_per_epoch=10, n_devices=4)
    assert step_cfg.grad_div == 2.0  # recipe fidelity preserved
    assert "EFFECTIVE learning rate" in caplog.text
    assert "--ngpu auto" in caplog.text

    banner = ngpu_mismatch_banner(2, 4, 0.5)
    assert "4/2" in banner and "~1" in banner  # 0.5 * 4/2 = 1.0


def test_resolve_loss_impl_reasoned_names_degradations(monkeypatch):
    from simclr_pytorch_distributed_tpu.train import supcon

    impl, reason = supcon.resolve_loss_impl_reasoned("auto", 256, 1)
    assert impl == "dense" and "non-TPU" in reason
    impl, reason = supcon.resolve_loss_impl_reasoned("dense", 256, 1)
    assert impl == "dense" and reason == "explicit request"
    impl, reason = supcon.resolve_loss_impl_reasoned(
        "auto", 256, 1, moco_queue=512
    )
    assert impl == "dense" and "moco_queue" in reason
    monkeypatch.setattr(supcon.jax, "default_backend", lambda: "tpu")
    impl, reason = supcon.resolve_loss_impl_reasoned("auto", 256, 1)
    assert impl == "fused" and "single-chip" in reason
    impl, reason = supcon.resolve_loss_impl_reasoned("auto", 3, 1)
    assert impl == "dense" and "tile" in reason


def test_impl_resolution_banner_format():
    line = impl_resolution_banner(
        "loss_impl", "auto", "dense", "non-TPU backend (cpu)"
    )
    assert line == (
        "[loss_impl] requested 'auto' -> resolved 'dense': non-TPU backend (cpu)"
    )
    same = impl_resolution_banner(
        "loss_impl", "dense", "dense", "explicit request"
    )
    assert same == "[loss_impl] 'dense': explicit request"


def test_build_logs_resolution_banners(tmp_path, caplog):
    """The loss's resolution is said at build; the encoder has one conv path
    and nothing to resolve, so no ``[conv_impl]`` line."""
    import logging

    from simclr_pytorch_distributed_tpu.train.supcon import build

    cfg = parse_supcon(
        ["--model", "resnet10", "--dataset", "synthetic", "--batch_size", "8",
         "--size", "8", "--workdir", str(tmp_path)]
    )
    with caplog.at_level(logging.INFO):
        build(cfg, steps_per_epoch=4, n_devices=1)
    assert "[loss_impl]" in caplog.text and "[conv_impl]" not in caplog.text


def test_telemetry_flag_both_parsers(tmp_path):
    """--telemetry {async,sync} on all three trainers' parsers; async is the
    default (the zero-sync hot loop)."""
    assert parse_supcon(["--workdir", str(tmp_path)]).telemetry == "async"
    assert parse_supcon(
        ["--telemetry", "sync", "--workdir", str(tmp_path)]
    ).telemetry == "sync"
    assert parse_linear(["--workdir", str(tmp_path)]).telemetry == "async"
    assert parse_linear(
        ["--telemetry", "sync", "--workdir", str(tmp_path)], ce=True
    ).telemetry == "sync"
    with pytest.raises(SystemExit):
        parse_supcon(["--telemetry", "never", "--workdir", str(tmp_path)])


def test_linear_parser_accepts_resume_for_launcher_contract():
    """Exit code 75's contract is 're-run the same command with --resume':
    the probe parser must accept the flag (retrain-from-scratch semantics)
    rather than die with 'unrecognized arguments'."""
    from simclr_pytorch_distributed_tpu import config as config_lib

    ns = config_lib.linear_parser(ce=False).parse_args(
        ["--dataset", "synthetic", "--resume", "/some/run_dir"]
    )
    assert ns.resume == "/some/run_dir"
    ns_ce = config_lib.linear_parser(ce=True).parse_args(
        ["--dataset", "synthetic", "--resume", "/some/run_dir"]
    )
    assert ns_ce.resume == "/some/run_dir"


def test_data_placement_flag_all_parsers(tmp_path):
    """--data_placement {host,device,auto} on all three trainers' parsers;
    'auto' (decide from the decoded dataset size, degrade to host with a
    banner) is the default everywhere."""
    assert parse_supcon(["--workdir", str(tmp_path)]).data_placement == "auto"
    assert parse_supcon(
        ["--data_placement", "device", "--workdir", str(tmp_path)]
    ).data_placement == "device"
    assert parse_linear(["--workdir", str(tmp_path)]).data_placement == "auto"
    assert parse_linear(
        ["--data_placement", "host", "--workdir", str(tmp_path)], ce=True
    ).data_placement == "host"
    with pytest.raises(SystemExit):
        parse_supcon(["--data_placement", "hbm", "--workdir", str(tmp_path)])


def test_data_placement_device_with_path_rejected_at_parse(tmp_path):
    """The 'device' x 'path' interaction dies AT PARSE TIME with the reason
    (folder trees may decode to an on-disk memmap above --mmap_threshold_mb,
    which residency refuses) — not deep in setup after the decode; 'auto'
    with path parses fine and resolves against the decoded array later."""
    path_args = ["--dataset", "path", "--data_folder", str(tmp_path),
                 "--mean", "(0.5,0.5,0.5)", "--std", "(0.5,0.5,0.5)",
                 "--workdir", str(tmp_path)]
    with pytest.raises(ValueError, match="memmap"):
        parse_supcon(["--data_placement", "device", *path_args])
    assert parse_supcon(
        ["--data_placement", "auto", *path_args]
    ).data_placement == "auto"
    # explicit 'window' x 'path' is FINE: the window store streams from a
    # memmap by construction, so the post-decode representation cannot
    # invalidate the request
    assert parse_supcon(
        ["--data_placement", "window", *path_args]
    ).data_placement == "window"


def test_window_placement_and_knobs_all_parsers(tmp_path):
    """--data_placement window plus the --data_window_batches /
    --device_budget_mb knobs on all three trainers' parsers; non-positive
    values die at parse time (the --ngpu convention — they feed a slice
    modulus and a byte budget)."""
    cfg = parse_supcon(
        ["--data_placement", "window", "--data_window_batches", "16",
         "--device_budget_mb", "2048", "--workdir", str(tmp_path)]
    )
    assert cfg.data_placement == "window"
    assert cfg.data_window_batches == 16 and cfg.device_budget_mb == 2048
    for ce in (False, True):
        lcfg = parse_linear(
            ["--data_placement", "window", "--data_window_batches", "4",
             "--device_budget_mb", "512", "--workdir", str(tmp_path)],
            ce=ce,
        )
        assert lcfg.data_placement == "window"
        assert lcfg.data_window_batches == 4
        assert lcfg.device_budget_mb == 512
    # defaults: window length 32, budget 0 = computed (0.4x free stats)
    d = parse_supcon(["--workdir", str(tmp_path)])
    assert d.data_window_batches == 32 and d.device_budget_mb == 0
    for bad_flag in ("--data_window_batches", "--device_budget_mb"):
        for bad in ("0", "-3", "x"):
            with pytest.raises(SystemExit):
                parse_supcon([bad_flag, bad, "--workdir", str(tmp_path)])
            with pytest.raises(SystemExit):
                parse_linear([bad_flag, bad, "--workdir", str(tmp_path)],
                             ce=True)


def test_budget_override_bytes_mapping():
    """The flag-to-resolver plumbing: MB -> bytes, 0 -> None (computed)."""
    from simclr_pytorch_distributed_tpu.data.device_store import (
        budget_override_bytes,
    )

    assert budget_override_bytes(0) is None
    assert budget_override_bytes(None) is None
    assert budget_override_bytes(1) == 1 << 20
    assert budget_override_bytes(2048) == 2048 << 20
