"""Compile-only checks of the main-path kernels for a TPU v5e.

The kernels are compiled (not interpreted) for a *described* v5e:2x2 chip at
llama32-1b widths, through the same ops wrappers serving calls: block
choice, padding and grouped-launch starts included. Nothing runs; a kernel
the chip's compiler refuses (unaligned blocks, scoped VMEM, ops Mosaic
cannot lower) fails here instead of on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantize import quantize_tensor
from repro.core.split import group_packed, split_quantize, split_quantize_packed
from repro.engine import autotune
from repro.kernels import ops

D, KV_W, FF, VOCAB = 2048, 512, 8192, 128256  # llama32-1b widths
DECODE_M, PREFILL_M = 8, 4 * 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(monkeypatch, one_chip):
    """Compile for the described chip: kernels compiled (not interpreted),
    the persistent cache off (a TPU entry cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield one_chip
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _packed(k, n):
    return jax.eval_shape(
        lambda w: split_quantize_packed(w, 4),
        jax.ShapeDtypeStruct((k, n), jnp.bfloat16))


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


# (name, K, member widths): one launch per row, as the grouped engine runs
PROJECTIONS = [
    ("wqkv", D, (D, KV_W, KV_W)),
    ("wo", D, (D,)),
    ("w_gateup", D, (FF, FF)),
    ("w_down", FF, (D,)),
    ("lm_head", D, (VOCAB,)),
]


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M], ids=["decode", "prefill"])
@pytest.mark.parametrize("name,k,widths", PROJECTIONS,
                         ids=[p[0] for p in PROJECTIONS])
def test_packed_matmul_compiles(compiled, name, k, widths, m):
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=compiled)
    members = [_packed(k, n) for n in widths]
    if len(members) > 1:
        grp = _on(compiled, jax.eval_shape(group_packed, members))
        _compile(ops.splitq_packed_group_matmul, x, grp)
    else:
        _compile(ops.splitq_packed_matmul, x, _on(compiled, members[0]))


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M], ids=["decode", "prefill"])
@pytest.mark.parametrize("name,k,widths", PROJECTIONS,
                         ids=[p[0] for p in PROJECTIONS])
def test_heuristic_blocks_fit_scoped_vmem(name, k, widths, m):
    """Every block the heuristic hands these shapes stays inside the
    autotuner's VMEM budget and tiles whole packing slabs."""
    max_bn = 512 if len(widths) > 1 else None
    bm, bn, bk = autotune.heuristic_block(m, k, sum(widths), 4,
                                          max_bn=max_bn, bf16_acts=True)
    assert bm % 16 == 0 and bn % 128 == 0 and bk % 128 == 0
    assert autotune._vmem_bytes(bm, bn, bk, 4) <= autotune.VMEM_BUDGET


def test_planes_and_linear_kernels_compile(compiled):
    """The paper's 3-plane kernel and the plain linear-quant kernel share
    the packed-tile unpack: both compile for the chip too."""
    x = jax.ShapeDtypeStruct((DECODE_M, D), jnp.bfloat16, sharding=compiled)
    w = jax.ShapeDtypeStruct((D, D), jnp.bfloat16)
    sq = _on(compiled, jax.eval_shape(lambda a: split_quantize(a, 4), w))
    _compile(ops.splitq_matmul, x, sq)
    qt = _on(compiled, jax.eval_shape(lambda a: quantize_tensor(a, 4), w))
    _compile(lambda a, t: ops.quant_matmul(a, t.packed, t.qp.scale,
                                           t.qp.zero, 4), x, qt)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_compiles(compiled, dtype):
    b, kv, g, hd, page, n_row = 8, 8, 4, 64, 16, 35
    pool = b * n_row
    s = compiled
    args = (
        jax.ShapeDtypeStruct((b, kv, g, hd), dtype, sharding=s),
        jax.ShapeDtypeStruct((pool, page, kv, hd), dtype, sharding=s),
        jax.ShapeDtypeStruct((pool, page, kv, hd), dtype, sharding=s),
        jax.ShapeDtypeStruct((b, n_row), jnp.int32, sharding=s),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=s),
    )
    _compile(ops.paged_attention, *args)
