import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Quantized-decode dry-run (Cell C of §Perf): lower the REAL packed
engine path — ``model.decode_step`` over an ``as_executable()`` tree of
``PackedSplitQTensor``/``PackedSplitQGroup`` containers — on the production
mesh, under the same exact-TP serve shardings ``BatchedServer --mesh``
executes with (``runtime.sharding.serve_param_specs`` +
``sharding_hints(exact_tp=True)``).

Nothing here is modeled: the lowered HLO contains the engine's in-graph
dequant + matmul exactly as serving runs it (codes/cids planes sharded on
the output dim, per-shard (S, Z) LUT reads replicated), the cache follows
the serving contract (per-slot ``len: (B,)``, slot dim batch-sharded over
``data``), and each Pallas kernel runs per device in a ``shard_map``
with its weights gathered for the call. Weight HBM traffic per decode step
drops from bf16 (16 bit/wt) to 6 bit/wt.

    PYTHONPATH=src python -m repro.launch.qserve_dryrun --arch internlm2-20b
"""
import argparse
import json
import pathlib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-20b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args(argv)

    import jax

    from repro.configs import SHAPES, get_config
    from repro.core.apply import restructure
    from repro.core.policy import QuantPolicy
    from repro.engine.autotune import choose_block
    from repro.launch.mesh import make_production_mesh
    from repro.models.attention import flash_fusion
    from repro.models.model import build_model
    from repro.roofline import analysis as roof
    from repro.roofline import hlocost
    from repro.runtime import sharding as shd
    from repro.runtime import steps

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    model = build_model(cfg)
    mesh = make_production_mesh()
    n_data, n_model = shd.mesh_dims(mesh)
    policy = QuantPolicy(bits=4, packed=True)

    aparams = steps.abstract_params(model)
    # Abstract executable tree via the production engine path — grouped
    # fused QKV / gate+up launches, exactly what the server jits.
    qparams_abs = jax.eval_shape(
        lambda p: restructure(p, policy).as_executable(group=True), aparams
    )

    def serve_step(qparams, tokens, cache):
        # hints entered INSIDE the traced body (trace-time capture), same
        # as BatchedServer's decode closure: exact-TP act_constraints plus
        # the kernels' shard_map over this mesh
        with shd.sharding_hints(mesh, exact_tp=True):
            return model.decode_step(qparams, tokens, cache)

    abatch = model.input_specs(shape)
    acache = model.cache_specs(shape)
    qpspecs = shd.serve_param_specs(qparams_abs, mesh)
    cspecs = shd.serve_cache_specs(acache, mesh)
    bspecs = shd.batch_specs(abatch, n_data, shd.dp_axes(mesh))
    ns = lambda t: jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )

    with mesh, flash_fusion(True):
        fn = jax.jit(
            serve_step,
            in_shardings=(ns(qpspecs), ns(bspecs["tokens"]), ns(cspecs)),
            donate_argnums=(2,),
        )
        lowered = fn.lower(qparams_abs, abatch["tokens"], acache)
        compiled = lowered.compile()

    lac = hlocost.analyze(compiled.as_text())
    coll = roof.collectives_from_ops(lac.collective_ops, mesh.size,
                                     pod_stride=1 << 30)
    n_params = roof.count_params(aparams)

    # The engine execution plan this lowering dispatched: block choices for
    # each distinct quantized matmul as a device runs it (its batch rows
    # over `data`, the whole projection — weights are gathered for the
    # kernel call), suitable for seeding SPLITQ_TUNE_CACHE.
    m_dec = max(1, shape.global_batch // n_data)  # decode: 1 token/sequence
    h, kv, hd, d, ff = (cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model,
                        cfg.d_ff)
    proj_shapes = {
        "wqkv": (d, h * hd + 2 * kv * hd),
        "wo": (h * hd, d),
        "w_gateup": (d, 2 * ff),
        "w_down": (ff, d),
    }
    engine_blocks = {
        name: list(choose_block(m_dec, k_, n_, policy.bits))
        for name, (k_, n_) in proj_shapes.items()
    }
    rec = {
        "arch": args.arch, "shape": args.shape,
        "mesh": f"{n_data}x{n_model}",
        "variant": "splitquantv2-int4-packed-decode",
        "status": "ok",
        "lowered": "engine-path decode_step (packed executables, "
                   "exact-TP serve shardings)",
        "cache_contract": "per-slot len (B,), per-row KV write offsets",
        "n_params": n_params,
        "t_compute_s": lac.flops / roof.PEAK_FLOPS,
        "t_memory_s": lac.bytes_min / roof.HBM_BW,
        "t_collective_s": (coll.wire_bytes_ici / roof.ICI_BW
                           + coll.wire_bytes_dcn / roof.DCN_BW),
        "bytes_min": lac.bytes_min,
        "coll_by_kind": coll.by_kind,
        "weight_bytes_bf16_per_dev": n_params * 2 / n_model,
        "weight_bytes_packed_per_dev": n_params * 6 / 8 / n_model,
        "engine_blocks": engine_blocks,
        "quant_launches_per_block": {"grouped": 4, "ungrouped": 7},
    }
    mem = compiled.memory_analysis()
    rec["per_device_peak_bytes"] = int(
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    p = out / f"{args.arch}__{args.shape}__qserve.json"
    p.write_text(json.dumps(rec, indent=2))
    print(json.dumps({k: v for k, v in rec.items()
                      if not isinstance(v, dict)}, indent=2))


if __name__ == "__main__":
    main()
