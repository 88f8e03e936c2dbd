"""Names of the token encoder's parameter tree against the reference's
(``reference_conv.param_spec``) for a preset of LFM2's block: a tree of
``encoder/block<i>/attn`` (the gated short convolution or grouped-query
attention), ``encoder/block<i>/mlp`` (a dense layer) or
``encoder/block<i>/moe`` (routed experts under a bias, no shared expert),
whose running statistics are ``encoder/block<i>/{prob,load}_mean`` and
``encoder/block<i>/moe/route_bias``.

``reference_name``, ``to_program`` and ``to_reference`` are
``adapter_latent``'s, reading this file's tables.
"""

from __future__ import annotations

import types

import adapter_latent

# both mixers' leaves under one table: only "norm" and "o" are shared
_ATTN = {"norm": "norm1", "o": "wo", "bcx": "w_bcx", "conv": "conv", "q": "wq", "k": "wk",
         "v": "wv", "q_norm": "q_norm", "k_norm": "k_norm"}
_MOE = {name: name for name in ("router", "w_gate", "w_up", "w_down", "route_bias")}
_MOE["norm"] = "norm2"
_PARTS = {"attn": _ATTN, "mlp": adapter_latent._MLP, "moe": _MOE}


def _with_our_tables(fn, **names):
    return types.FunctionType(fn.__code__, dict(vars(adapter_latent), _PARTS=_PARTS, **names),
                              fn.__name__, fn.__defaults__)


# ('encoder', 'block2', 'attn', 'bcx') -> 'layer2/w_bcx'
reference_name = _with_our_tables(adapter_latent.reference_name)
to_program = _with_our_tables(adapter_latent.to_program, reference_name=reference_name)
to_reference = _with_our_tables(adapter_latent.to_reference, reference_name=reference_name)
