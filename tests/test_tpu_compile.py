"""Compile-only checks of the main-path kernels for a TPU v5e.

The kernels are compiled (not interpreted) for a *described* v5e:2x2 chip at
llama32-1b widths, through the same ops wrappers serving calls: block
choice, padding and grouped-launch starts included. Nothing runs; a kernel
the chip's compiler refuses (unaligned blocks, scoped VMEM, ops Mosaic
cannot lower) fails here instead of on the chip. The serving decode and
prefill programs are compiled too, at qwen3-0.6b's attention widths, to
pin that they update the paged KV pool in place.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import dataclasses
import math
import re

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


def _kernel_names(text):
    """Names of the compiled Pallas kernel instructions, cut as profiles
    name their ops (``%paged_attention_pallas.1 = ...`` ->
    ``paged_attention_pallas``)."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z0-9_\-]+?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', text)}


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
        text = _compile(ops.splitq_packed_group_matmul, x, grp)
    else:
        text = _compile(ops.splitq_packed_matmul, x,
                        _on(compiled, members[0]))
    # the benchmark's trace reduction finds the kernel by this name
    names = _kernel_names(text)
    assert names and all(n.startswith("splitq_packed_matmul")
                         for n in names), names


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
    b, kv, g, hd, page, n_row, layers = 8, 8, 4, 64, 16, 35, 3
    pool = b * n_row
    s = compiled
    args = (
        jax.ShapeDtypeStruct((b, kv, g, hd), dtype, sharding=s),
        jax.ShapeDtypeStruct((layers, 2, pool, page, kv, hd), dtype,
                             sharding=s),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=s),
        jax.ShapeDtypeStruct((b, n_row), jnp.int32, sharding=s),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=s),
    )
    # the benchmark's trace reduction finds the kernel by this name
    names = _kernel_names(_compile(ops.paged_attention, *args))
    assert names and all(n.startswith("paged_attention")
                         for n in names), names


# the serving programs: qwen3-0.6b's attention widths at 2 layers, with a
# pool too large for the compiler to stage whole in fast memory (at a few
# MiB it copies the pool into VMEM, which says nothing about HBM traffic)
POOL_PAGES, PAGE, SLOTS, MAX_LEN, BUCKET = 2048, 16, 16, 256, 64
_VIEWS = {"parameter", "get-tuple-element", "bitcast"}
_OP = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                 r"([\w\-]+)\((.*)$", re.M)


def _pool_producers(text, pool):
    """(name, opcode, op_name) of every instruction whose result holds a
    whole pool's, one layer's or one K/V plane's elements of the pool's
    dtype; returned by kind."""
    whole = math.prod(pool.shape)
    layer, plane = whole // pool.shape[0], whole // (2 * pool.shape[0])
    kind = {whole: "pool", layer: "layer", plane: "plane"}
    found = {"pool": [], "layer": [], "plane": []}
    for m in _OP.finditer(text):
        name, dtype, dims, opcode, rest = m.groups()
        n = math.prod(int(d) for d in dims.split(",")) if dims else 1
        if dtype == pool.dtype.name.replace("bfloat16", "bf16") and n in kind:
            op_name = re.search(r'op_name="([^"]*)"', rest)
            found[kind[n]].append(
                (name, opcode, op_name.group(1) if op_name else ""))
    return found


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serving_programs_update_the_pool_in_place(compiled, monkeypatch,
                                                   program):
    """``BatchedServer``'s decode and prefill programs, as it jits them,
    update the paged pool in place: no op yields a layer's pool or a K/V
    plane, only views of the carried pool and the scatter of each layer's
    new K/V into it. Both take the pool donated and hand it back in the
    same buffer, with no second pool."""
    import repro.models.attention as attn_mod
    from repro.configs import get_config
    from repro.launch.serve import BatchedServer
    from repro.models import build_model

    monkeypatch.setattr(attn_mod, "_use_paged_kernel", lambda: True)
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), n_layers=2,
                              vocab_size=4096)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # the server holds a small pool; its programs compile for a large one
    server = BatchedServer(model, params, batch_slots=SLOTS,
                           max_len=MAX_LEN, paged=True, page_size=PAGE,
                           num_pages=64)
    s = compiled
    cache = _on(s, server.cache)
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, 2, POOL_PAGES, PAGE, cfg.n_kv_heads, cfg.hd),
        cache["pages"].dtype, sharding=s)
    cache["pages"] = pool

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=s)

    flags = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=s)
    if program == "decode":
        lowered = server._decode.lower(_on(s, params), i32(SLOTS, 1), cache,
                                       active=flags)
    else:
        lowered = server._prefill.lower(_on(s, params), i32(SLOTS, BUCKET),
                                        i32(SLOTS), flags, i32(SLOTS), cache)
    exe = lowered.compile()
    pool_bytes = math.prod(pool.shape) * pool.dtype.itemsize
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    assert mem.temp_size_in_bytes < pool_bytes, mem.temp_size_in_bytes
    found = _pool_producers(exe.as_text(), pool)
    assert not found["layer"] and not found["plane"], found
    copies = [f for f in found["pool"] if f[1] not in _VIEWS
              and not (f[1] in ("fusion", "scatter")
                       and f[2].rsplit("/", 1)[-1] == "scatter")]
    assert not copies, copies
    # the carried pool is there, updated by a scatter
    assert any(f[1] == "scatter" for f in found["pool"]), found["pool"]
