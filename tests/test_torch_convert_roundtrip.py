"""torch_convert round-trip: orbax -> reference .pth -> orbax, bit-identical.

The serving engine ingests reference ``.pth`` checkpoints through
``convert_reference_checkpoint``; this proves the converter pair is lossless
(pure transposes both ways), so `.pth` ingestion rests on a proven inverse
rather than on "the shapes happened to fit". Lazy-skips when torch is
unavailable (conversion is the only torch consumer in the repo).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_pytorch_distributed_tpu.models import SupConResNet
from simclr_pytorch_distributed_tpu.utils.checkpoint import (
    MODEL_LAYOUT_VERSION,
    _save_tree,
    _write_meta,
)
from simclr_pytorch_distributed_tpu.utils.torch_convert import (
    convert_reference_checkpoint,
    export_reference_checkpoint,
    torch_state_dict_to_variables,
    variables_to_torch_state_dict,
)

pytestmark = pytest.mark.serve


def _leaves_with_paths(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves_with_paths(tree[k], prefix + (k,))
    else:
        yield prefix, np.asarray(tree)


@pytest.fixture(scope="module")
def rn18_variables():
    # resnet18: the smallest architecture the reference's model_dict accepts
    # for export (resnet10 is a framework-only extension and is refused)
    model = SupConResNet(model_name="resnet18")
    v = model.init(jax.random.key(7), jnp.zeros((2, 8, 8, 3)), train=False)
    return {"params": v["params"], "batch_stats": v["batch_stats"]}


def test_state_dict_mapping_roundtrip_bit_identical(rn18_variables):
    """variables -> reference state_dict -> variables, no torch needed:
    every leaf returns bit-identical (the mappings are pure transposes)."""
    sd = variables_to_torch_state_dict(rn18_variables)
    back = torch_state_dict_to_variables(sd)
    orig = dict(_leaves_with_paths(rn18_variables))
    rt = dict(_leaves_with_paths(back))
    assert orig.keys() == rt.keys()
    for path, leaf in orig.items():
        np.testing.assert_array_equal(
            leaf, rt[path], err_msg="/".join(path)
        )


def test_export_rejects_an_encoder_the_reference_lacks():
    """A token encoder's blocks have no reference equivalent; export must
    fail loudly rather than write a silently-wrong .pth."""
    model = SupConResNet(model_name="keye-vl2-tiny")
    variables = model.init(jax.random.key(7), jnp.zeros((2, 16, 16, 3)), train=False)
    with pytest.raises(ValueError, match="cannot express encoder/"):
        variables_to_torch_state_dict(jax.tree.map(np.asarray, dict(variables)))


def test_export_import_roundtrip_bit_identical(tmp_path, rn18_variables):
    """Full on-disk loop through the reference's torch.save layout."""
    pytest.importorskip("torch")
    ckpt = tmp_path / "ckpt_epoch_3"
    _save_tree(str(ckpt / "model"), rn18_variables)
    _write_meta(str(ckpt), {"epoch": 3, "model_layout": MODEL_LAYOUT_VERSION})

    pth = tmp_path / "exported.pth"
    info = export_reference_checkpoint(str(ckpt), str(pth))
    assert info["model_name"] == "resnet18" and info["epoch"] == 3

    back_dir = tmp_path / "reimported"
    info2 = convert_reference_checkpoint(str(pth), str(back_dir))
    assert info2["model_name"] == "resnet18" and info2["epoch"] == 3

    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    restored = ckptr.restore(str(back_dir / "model"))
    ckptr.close()
    orig = dict(_leaves_with_paths(rn18_variables))
    rt = dict(_leaves_with_paths(restored))
    assert orig.keys() == rt.keys()
    for path, leaf in orig.items():
        np.testing.assert_array_equal(leaf, rt[path], err_msg="/".join(path))


def test_serving_engine_ingests_pth(tmp_path, rn18_variables):
    """The engine's `.pth` ingestion path: EmbeddingEngine.from_checkpoint on
    a reference-format file converts in place and infers the architecture."""
    pytest.importorskip("torch")
    from simclr_pytorch_distributed_tpu.serve.engine import EmbeddingEngine

    ckpt = tmp_path / "ckpt"
    _save_tree(str(ckpt / "model"), rn18_variables)
    _write_meta(str(ckpt), {"epoch": 1, "model_layout": MODEL_LAYOUT_VERSION})
    pth = tmp_path / "ref.pth"
    export_reference_checkpoint(str(ckpt), str(pth))

    eng = EmbeddingEngine.from_checkpoint(str(pth), buckets=(2,))
    assert eng.model.model_name == "resnet18"
    assert eng.feat_dim == 512
    assert (tmp_path / "ref.pth.converted" / "model").is_dir()
