"""Compressed gradient collectives with error feedback.

At 1000+ node scale the cross-pod (DCN) gradient reduction dominates step
time for DP-heavy meshes. ``compressed_psum_mean`` implements the classic
int8 error-feedback scheme as explicit per-shard collectives under
``shard_map``:

  1. residual-corrected gradient  g' = g + e   (error feedback carry)
  2. per-block int8 quantize (block=256, symmetric, max-abs scale)
  3. reduce-scatter of int8 payloads + fp32 block scales  — each hop moves
     ~25% of the fp32 bytes
  4. local fp32 reduction of the dequantized shards
  5. int8 all-gather of the reduced shard
  6. new residual  e' = g' − dequant(quant-roundtrip applied to g')

Error feedback makes the *accumulated* bias vanish: quantization error is
re-injected next step, so SGD/Adam trajectories track the uncompressed run
(property-tested in tests/test_compression.py). The collective-byte saving
is measured from lowered HLO in benchmarks/compression_bench.py.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

BLOCK = 256


def _quant_block(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-block int8. x: (n,) padded to BLOCK multiple."""
    xb = x.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(xb), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequant_block(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * scale).reshape(-1)


def quant_roundtrip(x: jax.Array) -> jax.Array:
    pad = (-x.size) % BLOCK
    flat = jnp.pad(x.reshape(-1), (0, pad))
    q, s = _quant_block(flat)
    out = _dequant_block(q, s)
    return out[: x.size].reshape(x.shape)


def compressed_psum_mean(
    flat_grad: jax.Array, axis_name: str
) -> jax.Array:
    """Mean over ``axis_name`` with int8 wire traffic (call inside
    shard_map). flat_grad: (n,) fp32, size divisible by BLOCK and by the
    axis size."""
    n_shards = jax.lax.axis_size(axis_name)
    q, s = _quant_block(flat_grad)
    nblk = q.shape[0]
    # reduce-scatter decomposition: all_to_all int8 chunks, local fp32 sum
    qs = q.reshape(n_shards, nblk // n_shards, BLOCK)
    ss = s.reshape(n_shards, nblk // n_shards, 1)
    q_x = jax.lax.all_to_all(qs, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    s_x = jax.lax.all_to_all(ss, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    local = jnp.sum(
        q_x.astype(jnp.float32) * s_x.astype(jnp.float32), axis=0
    )  # (nblk/n, BLOCK) fp32 partial sums, exact in fp32
    local = local / n_shards
    # re-quantize the reduced shard, all-gather int8 + scales
    lq, lscale = _quant_block(local.reshape(-1))
    gq = jax.lax.all_gather(lq, axis_name, axis=0, tiled=True)
    gs = jax.lax.all_gather(lscale, axis_name, axis=0, tiled=True)
    return _dequant_block(gq, gs)[: flat_grad.size]


def flatten_tree(tree: Any) -> tuple[jax.Array, Any, list, list]:
    flat, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [x.size for x in flat]
    shapes = [x.shape for x in flat]
    big = jnp.concatenate([x.reshape(-1).astype(jnp.float32) for x in flat])
    return big, treedef, sizes, shapes


def unflatten_tree(big: jax.Array, treedef, sizes, shapes) -> Any:
    outs, off = [], 0
    for sz, shp in zip(sizes, shapes):
        outs.append(big[off : off + sz].reshape(shp))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, outs)


def make_compressed_allreduce(mesh: Mesh, axis_name: str = "data"):
    """Returns f(per_shard_grads, err) -> (mean, new_err).

    per_shard_grads: pytree whose leaves are stacked per-shard gradients
    with leading dim == mesh.shape[axis_name] (the shard_map DP layout);
    err: same-structure error-feedback residual, PER SHARD (leading dim
    too) — each shard corrects its own compression error.
    Wire traffic per hop is int8 + fp32/BLOCK scales ≈ 26.6% of fp32.
    """
    n_ax = mesh.shape[axis_name]

    def allreduce(tree: Any, err: Any):
        big, treedef, sizes, shapes = flatten_tree(tree)      # (n_ax * n,)
        ebig, *_ = flatten_tree(err)
        n = big.size // n_ax
        pad = (-n) % (BLOCK * n_ax)
        big2 = (big + ebig).reshape(n_ax, n)
        big2 = jnp.pad(big2, ((0, 0), (0, pad)))

        def inner(g):
            g = g[0]  # (n+pad,) this shard's corrected gradient
            reduced = compressed_psum_mean(g, axis_name)
            new_err = g - quant_roundtrip(g)  # local quantization residual
            return reduced[None], new_err[None]

        reduced, new_err = jax.shard_map(
            inner, mesh=mesh,
            in_specs=P(axis_name, None),
            out_specs=(P(None), P(axis_name, None)),
            check_vma=False,
        )(big2)
        reduced = reduced[0, : n]
        mean = jnp.tile(reduced, n_ax)[: big.size]
        new_err_flat = new_err[:, :n].reshape(-1)[: big.size]
        return (
            unflatten_tree(mean, treedef, sizes, shapes),
            unflatten_tree(new_err_flat, treedef, sizes, shapes),
        )

    return allreduce
