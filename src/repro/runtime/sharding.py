"""PartitionSpec rule engine: DP / TP / SP / EP / ZeRO-1 over the
(pod, data, model) production mesh.

Parameters are matched by path substring (first rule wins). Conventions:

* TP (Megatron, train/analysis mode): attention/MLP in-projections
  column-parallel (out dim on ``model``), out-projections row-parallel (in
  dim on ``model``); vocab sharded on ``model`` for embed/unembed; MoE
  experts sharded on ``model`` (classic EP: the dispatch scatter/gather
  becomes the all-to-all).
* Serving ("exact TP", :func:`serve_param_specs`): every matched weight —
  including packed ``PackedSplitQTensor``/``PackedSplitQGroup`` code and
  cluster-id planes — shards its OUTPUT (last) dim over ``model`` while the
  per-shard (S, Z) LUTs stay replicated, and :func:`act_constraint`
  replicates matmul inputs/outputs over ``model``. Contraction dims are
  never sharded, so GSPMD only ever inserts value-exact all-gathers (no
  partial-sum all-reduces) and greedy streams stay bit-identical to the
  single-device path.
* DP: params replicated over ``pod``/``data``; the batch dim of inputs and
  caches shards over ``("pod", "data")``.
* ZeRO-1: optimizer master/m/v additionally shard over ``data`` on the
  largest still-unsharded axis (uneven sizes fine — GSPMD pads).
* SP: the residual stream is constrained to P(batch, "model", None) between
  blocks (sequence-parallel) via :func:`act_constraint`, an ambient-mesh
  no-op outside pjit.

All divisibility checks come from the mesh instance (or explicit
``n_model``/``n_data``) passed in — there is no module-global mesh state,
so two meshes of different shapes can coexist in one process.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (substring, spec-builder(shape, n_model) -> P). Checked in order.
# Leading L axis (stacked layers) is never sharded.
_RULES: list[tuple[str, Any]] = []


def _rule(substr):
    def deco(fn):
        _RULES.append((substr, fn))
        return fn
    return deco


def _div(n: int, by: int) -> bool:
    return by > 0 and n % by == 0 and n >= by


# pjit *argument* shardings require exact divisibility (unlike
# intermediates, which GSPMD pads) — every rule checks before sharding.
def _last_on_model(shape, nm):
    if _div(shape[-1], nm):
        return P(*([None] * (len(shape) - 1) + ["model"]))
    if len(shape) >= 2 and _div(shape[-2], nm):
        return P(*([None] * (len(shape) - 2) + ["model", None]))
    return P()


def _secondlast_on_model(shape, nm):
    if _div(shape[-2], nm):
        return P(*([None] * (len(shape) - 2) + ["model", None]))
    if _div(shape[-1], nm):
        return P(*([None] * (len(shape) - 1) + ["model"]))
    return P()


# --- embeddings / heads: vocab on model (fallback: d_model) -----------------
@_rule("embed/table")
def _(shape, nm):
    if _div(shape[0], nm):
        return P("model", None)
    if _div(shape[1], nm):
        return P(None, "model")  # whisper: 51865 vocab not 16-divisible
    return P()


@_rule("lm_head/w")
def _(shape, nm):
    if _div(shape[1], nm):
        return P(None, "model")
    if _div(shape[0], nm):
        return P("model", None)
    return P()


# --- MoE (before generic attn/mlp rules) -------------------------------------
@_rule("moe/router")
def _(shape, nm):
    return P()  # tiny + routing-critical: replicated


def _experts(shape, nm):
    # (L, E, D, F): EP over experts when E divides, else F on model
    if _div(shape[1], nm):
        return P(None, "model", None, None)
    return P(None, None, None, "model") if _div(shape[3], nm) else P()


@_rule("experts/w_up")
def _(shape, nm):
    return _experts(shape, nm)


@_rule("experts/w_gate")
def _(shape, nm):
    return _experts(shape, nm)


@_rule("experts/w_down")
def _(shape, nm):
    return _experts(shape, nm)


# --- attention ---------------------------------------------------------------
@_rule("attn/wq")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("attn/wk")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("attn/wv")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("attn/wo")
def _(shape, nm):
    return _secondlast_on_model(shape, nm)


# --- dense MLP ---------------------------------------------------------------
@_rule("w_gate")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("w_up")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("w_down")
def _(shape, nm):
    return _secondlast_on_model(shape, nm)


# --- mamba2 -------------------------------------------------------------------
@_rule("in_proj")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("out_proj")
def _(shape, nm):
    return _secondlast_on_model(shape, nm)


@_rule("conv_w")
def _(shape, nm):
    return _last_on_model(shape, nm)  # depthwise channels on model


@_rule("conv_b")
def _(shape, nm):
    return _last_on_model(shape, nm)


# --- rwkv6 --------------------------------------------------------------------
@_rule("cm_wk")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("cm_wv")
def _(shape, nm):
    return _secondlast_on_model(shape, nm)


@_rule("cm_wr")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("tmix/wr")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("tmix/wk")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("tmix/wv")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("tmix/wg")
def _(shape, nm):
    return _last_on_model(shape, nm)


@_rule("tmix/wo")
def _(shape, nm):
    return _secondlast_on_model(shape, nm)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            # GetAttrKey: fields of registered dataclasses — this is how the
            # packed containers (codes/cids/scales/zeros) show up in trees
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def mesh_dims(mesh: Mesh) -> tuple[int, int]:
    """(n_data_total incl. pod, n_model) of a concrete mesh instance."""
    n_data = 1
    for a in BATCH_AXES:
        n_data *= mesh.shape.get(a, 1)
    return n_data, mesh.shape.get("model", 1)


# Tensors above this size additionally shard over `data` (FSDP / ZeRO-3
# style): llama4-scout's 100B expert bank cannot live TP-sharded only.
FSDP_THRESHOLD = 2 * 1024**3  # elements


def _add_data_axis(spec: P, shape: tuple[int, ...], n_data: int) -> P:
    """Shard the largest data-axis-divisible unsharded dim over `data`."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    if "data" in parts:  # FSDP already claimed the data axis
        return P(*parts)
    best, best_size = None, 1
    for i, (pt, s) in enumerate(zip(parts, shape)):
        if pt is None and s > best_size and _div(s, n_data):
            best, best_size = i, s
    if best is None:
        return P(*parts)
    parts[best] = "data"
    return P(*parts)


def param_spec(path: str, shape: tuple[int, ...], *,
               n_model: int, n_data: int) -> P:
    spec = None
    for substr, fn in _RULES:
        if substr in path:
            spec = fn(shape, n_model)
            break
    if spec is None:
        return P()  # norms, scalars, time_* vectors: replicated
    size = 1
    for s in shape:
        size *= s
    if size >= FSDP_THRESHOLD:
        spec = _add_data_axis(spec, shape, n_data)
    return spec


def param_specs(params: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree mirroring a param (or abstract param) pytree,
    with divisibility checked against the given mesh instance."""
    nd, nm = mesh_dims(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef,
        [param_spec(_path_str(p), tuple(l.shape), n_model=nm, n_data=nd)
         for p, l in flat],
    )


def zero1_spec(spec: P, shape: tuple[int, ...], n_data: int) -> P:
    """Add 'data' sharding on the largest divisible unsharded dim (ZeRO-1)."""
    return _add_data_axis(spec, shape, n_data)


def opt_specs(params: Any, mesh: Mesh) -> dict:
    """Sharding spec tree for the AdamW state of ``params``."""
    nd, nm = mesh_dims(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    zflat = [
        zero1_spec(
            param_spec(_path_str(p), tuple(l.shape), n_model=nm, n_data=nd),
            tuple(l.shape), nd,
        )
        for p, l in flat
    ]
    ztree = jax.tree_util.tree_unflatten(treedef, zflat)
    return {"step": P(), "master": ztree, "m": ztree, "v": ztree}


# ---------------------------------------------------------------------------
# Serving ("exact TP") param specs — packed containers included
# ---------------------------------------------------------------------------

# Weight names whose LAST dim is the matmul output dim in serving. Sharding
# only output dims keeps every contraction local to a device: the all-gather
# GSPMD inserts to re-replicate the product is value-exact, unlike the
# partial-sum all-reduce a row-parallel (contraction-sharded) layout needs.
_SERVE_LAST = (
    "attn/wq", "attn/wk", "attn/wv", "attn/wo", "attn/wqkv",
    "w_gate", "w_up", "w_gateup", "w_down",
    "lm_head/w", "in_proj", "out_proj",
)
# Quantized container planes: codes/cids pack along N (the output dim), so
# they shard exactly like the dense weight; the k-entry (S, Z) LUTs are a
# few floats per member and stay replicated — each shard reads its own
# device-local code plane against a local LUT copy.
_PACKED_SHARDED = ("codes", "cids", "qcodes", "planes")
_PACKED_REPLICATED = ("scales", "zeros", "info", "meta")


def serve_param_spec(path: str, shape: tuple[int, ...], n_model: int) -> P:
    """Output-stationary spec for one (possibly packed-container) leaf."""
    leafname = path.rsplit("/", 1)[-1]
    if "embed/table" in path:
        # one-hot @ table: a vocab-sharded contraction is exact (all partial
        # rows are exact zeros), and vocab is the big dim — shard it.
        if _div(shape[0], n_model):
            return P("model", *([None] * (len(shape) - 1)))
        if _div(shape[-1], n_model):
            return P(*([None] * (len(shape) - 1) + ["model"]))
        return P()
    matched = any(s in path for s in _SERVE_LAST)
    if not matched:
        return P()  # norms, rwkv/moe (follow-on), conv, scalars: replicated
    if leafname in _PACKED_REPLICATED:
        return P()
    # dense weight or a packed codes/cids plane: both keep N last
    if _div(shape[-1], n_model):
        return P(*([None] * (len(shape) - 1) + ["model"]))
    return P()


def serve_param_specs(params: Any, mesh: Mesh) -> Any:
    """Bit-exact-TP spec tree for an ``as_executable()`` (or fp) param tree."""
    _, nm = mesh_dims(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef,
        [serve_param_spec(_path_str(p), tuple(l.shape), nm) for p, l in flat],
    )


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------

BATCH_AXES = ("pod", "data")


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """The data-parallel mesh axes actually present (pod is optional)."""
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)


def batch_specs(batch_like: Any, n_batch_shards: int,
                axes: tuple[str, ...] = BATCH_AXES) -> Any:
    """Shard the leading (batch) dim over pod×data when exactly divisible."""
    def spec(leaf):
        b = leaf.shape[0] if leaf.ndim else 1
        if _div(b, n_batch_shards):
            return P(axes, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))
    return jax.tree.map(spec, batch_like)


def cache_specs_tree(cache_like: Any, *, long_context: bool,
                     axes: tuple[str, ...] = BATCH_AXES,
                     n_dp: int = 1, n_model: int = 1,
                     decode: bool = False) -> Any:
    """KV caches: batch over pod×data. The model-axis placement is the
    decode-critical choice:

    * decode: the cache SEQUENCE dim shards over `model` (context-parallel
      decode) — per-shard partial attention combines with tiny per-head
      collectives, and the cache is NEVER gathered. Sharding kv-heads (or
      head_dim when GQA heads don't divide the TP degree) instead makes
      GSPMD all-gather the entire cache every token (~107 GB/step at
      internlm2 decode_32k — measured, see EXPERIMENTS §Perf).
    * prefill: kv-heads over model (head_dim fallback) — queries attend
      densely anyway and the head-parallel layout writes without traffic.
    * batch-1 long-context decode: sequence over `data` too."""

    def _kv_dims(kv: int, hd: int):
        if _div(kv, n_model):
            return "model", None
        if _div(hd, n_model):
            return None, "model"
        return None, None

    def spec(path, leaf):
        name = _path_str(path).split("/")[-1]
        shp = leaf.shape
        if name in ("kv", "shared_kv") and leaf.ndim == 6:
            # (L, 2, B, S, KV, hd)
            _, _, b, s, kv, hd = shp
            if long_context:
                seq = "data" if _div(s, n_dp) else None
                if decode and _div(s // max(n_dp, 1), n_model):
                    return P(None, None, None, ("data", "model"), None, None)
                return P(None, None, None, seq, *(_kv_dims(kv, hd)))
            bsp = axes if _div(b, n_dp) else None
            if decode and _div(s, n_model):
                return P(None, None, bsp, "model", None, None)
            kvs, hds = _kv_dims(kv, hd)
            return P(None, None, bsp, None, kvs, hds)
        if name in ("cross_k", "cross_v") and leaf.ndim == 5:
            # (L, B, S, KV, hd)
            _, b, s, kv, hd = shp
            kvs, hds = _kv_dims(kv, hd)
            if decode and _div(s, n_model):
                kvs, hds = None, None
                bsp = axes if _div(b, n_dp) else None
                return P(None, bsp, "model", kvs, hds)
            bsp = axes if (_div(b, n_dp) and not long_context) else None
            return P(None, bsp, None, kvs, hds)
        if name in ("ssm", "wkv") and leaf.ndim == 5:
            # (L, B, H, N, P)
            _, b, h, _, _ = shp
            bsp = axes if (_div(b, n_dp) and not long_context) else None
            hsp = "model" if _div(h, n_model) else None
            return P(None, bsp, hsp, None, None)
        if name in ("conv", "shift_t", "shift_c") and leaf.ndim >= 3:
            # (L, B, K-1, C) / (L, B, D): channels on model
            ch = "model" if _div(shp[-1], n_model) else None
            b = shp[1]
            bsp = axes if (_div(b, n_dp) and not long_context) else None
            return P(None, bsp, *([None] * (leaf.ndim - 3)), ch)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_like)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat]
    )


def serve_cache_specs(cache_like: Any, mesh: Mesh) -> Any:
    """Spec tree for a serving cache (paged or dense) on a (data, model) mesh.

    Everything batch-shards its slot dim over the data axes; the page pool's
    PAGE dim shards over data too, so each DP replica's pages — and its
    ``cow()``/``copy_page()``/``rewind`` traffic — are device-local. Nothing
    lands on ``model`` (the exact-TP serving layout replicates activations
    over ``model``, so a model-sharded cache would just bounce)."""
    n_dp, _ = mesh_dims(mesh)
    axes = dp_axes(mesh)

    def spec(path, leaf):
        name = _path_str(path).split("/")[-1]
        shp = tuple(leaf.shape)
        nd = len(shp)

        def bat(dim):
            if _div(shp[dim], n_dp):
                return P(*[axes if i == dim else None for i in range(nd)])
            return P(*([None] * nd))

        if name in ("pages", "shared_pages") and nd == 6:
            return bat(2)       # (L, 2, PAGES, page, KV, hd): pool over data
        if name in ("kv", "shared_kv") and nd == 6:
            return bat(2)       # (L, 2, B, S, KV, hd)
        if name in ("cross_k", "cross_v") and nd == 5:
            return bat(1)
        if name in ("ssm", "wkv") and nd == 5:
            return bat(1)
        if name in ("conv", "shift_t", "shift_c") and nd >= 3:
            return bat(1)
        if name == "page_table" and nd == 2:
            return bat(0)       # (B, pages_per_row)
        if name == "len" and nd == 1:
            return bat(0)
        return P(*([None] * nd))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_like)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat]
    )


# ---------------------------------------------------------------------------
# Activation constraints (SP / exact-TP) — ambient mesh context
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def sharding_hints(mesh: Mesh, exact_tp: bool = False):
    """Ambient mesh for :func:`act_constraint`.

    ``exact_tp=True`` switches to the serving contract: matmul inputs and
    outputs replicate over ``model`` (only exact all-gathers, bit-identical
    streams) and Pallas kernels run per device under a ``shard_map``
    (:func:`serving_mesh`, ``repro.kernels.ops``)."""
    prev = (getattr(_CTX, "mesh", None), getattr(_CTX, "exact", False))
    _CTX.mesh, _CTX.exact = mesh, exact_tp
    try:
        yield
    finally:
        _CTX.mesh, _CTX.exact = prev


def serving_mesh() -> Mesh | None:
    """The mesh of the exact-TP serving hints the current trace runs
    under, or None outside them."""
    if not getattr(_CTX, "exact", False):
        return None
    return getattr(_CTX, "mesh", None)


def _batch_axes_of(mesh: Mesh):
    return BATCH_AXES if "pod" in mesh.axis_names else ("data",)


def act_constraint(x: jax.Array, kind: str) -> jax.Array:
    """Constrain intermediate activations; no-op without ambient mesh.

    Train/analysis kinds: "residual" (B, S, D) -> sequence-parallel
    P(batch, model, None); "logits" (B, S, V) -> vocab on model; plus
    heads/tokens2d/expert_buf/heads5 (see body). Under ``exact_tp`` serving
    hints, "residual"/"logits"/"matmul_io" pin batch-over-data with
    everything else replicated (value-exact collectives only) and the
    remaining kinds are no-ops.
    """
    mesh = getattr(_CTX, "mesh", None)
    if mesh is None:
        return x
    batch = _batch_axes_of(mesh)
    n_model = mesh.shape.get("model", 1)
    if getattr(_CTX, "exact", False):
        if kind not in ("residual", "logits", "matmul_io"):
            return x
        n_dp, _ = mesh_dims(mesh)
        if x.ndim < 1:
            return x
        bdim = batch if _div(x.shape[0], n_dp) else None
        spec = P(bdim, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    if kind == "residual" and x.ndim == 3:
        bdim = batch if x.shape[0] > 1 else None
        spec = P(bdim, "model", None) if x.shape[1] > 1 else P(bdim, None, None)
    elif kind == "logits" and x.ndim == 3:
        spec = P(batch if x.shape[0] > 1 else None, None, "model")
    elif kind == "heads" and x.ndim == 4:
        # (B, S, H, hd): shard heads over model when they divide
        if x.shape[2] % n_model:
            return x
        bdim = batch if x.shape[0] > 1 else None
        spec = P(bdim, None, "model", None)
    elif kind == "tokens2d" and x.ndim == 2:
        # (T, D) flattened token stream (MoE dispatch/combine): keep fully
        # sharded over data x model so the combine lowers to reduce-scatter
        # instead of a full all-reduce of (T, D)
        if x.shape[0] % (mesh.shape.get("data", 1) * n_model):
            return x
        spec = P((*batch, "model"), None)
    elif kind == "expert_buf" and x.ndim == 3:
        # (E, C, D): experts over model (EP)
        if x.shape[0] % n_model:
            return x
        spec = P("model", None, None)
    elif kind == "heads5" and x.ndim == 5:
        # (B, n_chunks, Q, H, hd): stacked q-chunk layout — pin the head
        # sharding so the scan xs don't bounce through replication
        if x.shape[3] % n_model:
            return x
        bdim = batch if x.shape[0] > 1 else None
        spec = P(bdim, None, None, "model", None)
    else:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# MeshPlan — one serving run's placement plan on one mesh instance
# ---------------------------------------------------------------------------


class MeshPlan:
    """Placement plan binding one ``BatchedServer`` run to one (data, model)
    mesh: canonical NamedShardings for params / cache / host batch arrays,
    plus the trace-time hints context. Holds no global state — two plans on
    two meshes coexist."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.axes = dp_axes(mesh)
        self.n_data, self.n_model = mesh_dims(mesh)

    def hints(self):
        return sharding_hints(self.mesh, exact_tp=True)

    def ns(self, spec_tree: Any) -> Any:
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    def put_params(self, params: Any) -> tuple[Any, Any]:
        """(device_put tree, sharding tree) under the exact-TP serve rules."""
        shd = self.ns(serve_param_specs(params, self.mesh))
        return jax.device_put(params, shd), shd

    def cache_shardings(self, cache: Any) -> Any:
        return self.ns(serve_cache_specs(cache, self.mesh))

    def put_cache(self, cache: Any, shardings: Any) -> Any:
        """(Re-)commit a cache tree to its canonical shardings.

        device_put on an already-matching leaf is a no-op; after host-side
        eager edits (page-table upload, COW page copies, snapshot installs)
        it restores the canonical layout so jitted-call input shardings stay
        byte-stable and decode compiles exactly once."""
        return jax.tree.map(jax.device_put, cache, shardings)

    def put_batch(self, arr: Any) -> jax.Array:
        """Host array -> device, leading dim over data when divisible."""
        a = np.asarray(arr)
        if a.ndim and _div(a.shape[0], self.n_data):
            spec = P(self.axes, *([None] * (a.ndim - 1)))
        else:
            spec = P(*([None] * a.ndim))
        return jax.device_put(a, NamedSharding(self.mesh, spec))

    def put_replicated(self, arr: Any) -> jax.Array:
        a = np.asarray(arr)
        return jax.device_put(
            a, NamedSharding(self.mesh, P(*([None] * a.ndim))))
