"""Whole-model restructuring pass — SplitQuantV2 over a parameter pytree.

``restructure(params, policy)`` walks any pytree of arrays, applies
SplitQuantV2 (or the plain linear-quant baseline) to every leaf the policy
selects, and returns a :class:`QuantizedModel` holding quantized leaves +
untouched leaves. ``materialize()`` rebuilds an ordinary param pytree with
*effective* (dequantized) weights so any model in the zoo runs unchanged —
this is exactly the fake-quant semantics the paper evaluates, while the
serving path can route selected matmuls through the packed Pallas kernels.

Stacked layers (leading scan axis of size L) are handled by vmapping the
per-tensor transform over the leading axis: each layer gets its *own*
clustering and scales, matching the paper's layer-by-layer processing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import split as split_mod
from repro.core.policy import QuantPolicy
from repro.core.quantize import QTensor, quantize_tensor


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


@dataclass
class QuantizedModel:
    """Result of the restructuring pass."""

    qleaves: dict[str, Any]          # path -> QTensor | SplitQTensor | Packed
    passthrough: dict[str, jax.Array]
    treedef: Any
    paths: list[str]                 # leaf order for reconstruction
    stacked: dict[str, bool]         # path -> had leading layer axis
    policy: QuantPolicy
    dtypes: dict[str, Any]           # path -> dtype of the original leaf

    def materialize(self) -> Any:
        """Params pytree with effective (dequantized) weights, each in the
        dtype of the leaf it replaces (a bf16 model stays bf16)."""
        leaves = []
        for p in self.paths:
            if p in self.qleaves:
                qt = self.qleaves[p]
                if self.stacked[p]:
                    w = jax.vmap(lambda t: t.dequantize())(qt)
                else:
                    w = qt.dequantize()
                leaves.append(w.astype(self.dtypes[p]))
            else:
                leaves.append(self.passthrough[p])
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def as_executable(self, *, group: bool = True) -> Any:
        """Params-like pytree with hot-path leaves kept in packed storage.

        The model forward routes these through the packed Pallas kernels
        (see repro.engine) — real 6-bit weight streaming instead of the
        fake-quant dense weights ``materialize()`` rebuilds. With
        ``group=True``, sibling projections are fused (wq/wk/wv -> wqkv,
        w_gate/w_up -> w_gateup) so a decode block costs 4 quantized kernel
        launches instead of 7."""
        from repro.engine.executable import build_executable

        return build_executable(self, group=group)

    def size_bytes(self) -> dict[str, int]:
        """Storage accounting (reproduces the paper's 3/8-of-FP32 claim)."""
        def nbytes(t):
            return sum(
                np.prod(l.shape) * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(t)
            )
        q = int(sum(nbytes(v) for v in self.qleaves.values()))
        rest = int(sum(nbytes(v) for v in self.passthrough.values()))
        return {"quantized": q, "passthrough": rest, "total": q + rest}


def _transform_leaf(w: jax.Array, policy: QuantPolicy, stacked: bool):
    def one(t):
        if not policy.split:
            return quantize_tensor(t, policy.bits)
        if policy.packed:
            return split_mod.split_quantize_packed(t, policy.bits, k=policy.k)
        return split_mod.split_quantize(t, policy.bits, k=policy.k)

    if stacked:
        return jax.vmap(one)(w)
    return one(w)


def restructure(
    params: Any,
    policy: QuantPolicy | None = None,
    *,
    stacked_axis_paths: Callable[[str], bool] | None = None,
) -> QuantizedModel:
    """Apply SplitQuantV2 (per ``policy``) to every selected leaf.

    stacked_axis_paths: predicate marking leaves whose axis 0 is a scan/layer
      axis (each slice is an independent layer → independent clustering).
      Default: any selected leaf with ndim >= 3 whose path contains "layers".
    """
    policy = policy or QuantPolicy()
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    qleaves: dict[str, Any] = {}
    passthrough: dict[str, jax.Array] = {}
    paths: list[str] = []
    stacked: dict[str, bool] = {}
    dtypes: dict[str, Any] = {}

    for path, leaf in flat:
        p = _path_str(path)
        paths.append(p)
        leaf = jnp.asarray(leaf)
        if policy.wants(p, leaf.ndim, leaf.size):
            if stacked_axis_paths is not None:
                is_stacked = stacked_axis_paths(p) and leaf.ndim >= 3
            else:
                is_stacked = leaf.ndim >= 3 and "layers" in p.lower()
            qleaves[p] = _transform_leaf(leaf, policy, is_stacked)
            stacked[p] = is_stacked
            dtypes[p] = leaf.dtype
        else:
            passthrough[p] = leaf
            stacked[p] = False
    return QuantizedModel(
        qleaves=qleaves, passthrough=passthrough, treedef=treedef,
        paths=paths, stacked=stacked, policy=policy, dtypes=dtypes,
    )


def quantize_model(params: Any, bits: int, *, split: bool = True,
                   packed: bool = False, k: int = 3) -> Any:
    """One-call fake-quant: restructure + materialize effective weights."""
    qm = restructure(params, QuantPolicy(bits=bits, split=split, packed=packed, k=k))
    return qm.materialize()
