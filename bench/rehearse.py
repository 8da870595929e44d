"""Compile-only rehearsal: the biggest programs of a configuration, for
a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config granite-3-2b --lanes 8,16

For each lane count it compiles, at published width, the served decode
block (full slot window, the fused engine that `fused="auto"` resolves
to on a TPU) and the largest prefill programs (a lone prompt and a
group of `lanes` prompts at the configuration's largest bucket), prints
each program's `memory_analysis()`, and the reckoning

    weights + decode state + max(decode temporaries,
                                 prefill temporaries + prefill outputs)

against the chip's HBM (the served decode block donates its state, so
its output is written in place). The answer fixes `serve.lanes` in the
configuration file. Nothing executes and nothing is cached.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--lanes", default="8,16")
    ap.add_argument("--bucket", type=int, default=4096)
    ap.add_argument("--reference", type=int, default=1, choices=(0, 1))
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, spec, weights
    from repro.core import attention
    from repro.kernels import ops
    from repro.launch import serve

    jax.config.update("jax_enable_compilation_cache", False)
    # the described chip is not the backend JAX runs on here: steer the
    # engine choice to what `fused="auto"` picks on a TPU
    ops._on_tpu = lambda: True
    attention.fused_auto_decision = lambda: {"engine": "fused"}

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = spec.config(args.config)
    hbm = harness.peak_table(topo.devices[0].device_kind)["hbm_bytes"]
    model = harness.program_model(cfg)
    key = serve._model_key(model)
    m = cfg["model"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    wshape = {k: sds(s, jnp.bfloat16)
              for k, (s, _) in weights.shapes(m).items()}
    params = on_chip(jax.eval_shape(
        lambda w: weights.program_tree(w, m), wshape))
    wbytes = sum(a.size * 2 for a in wshape.values())
    block = cfg["serve"]["block"]

    def analyse(fn, *a):
        mem = fn.lower(*a).compile().memory_analysis()
        return {k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}

    out = {"config": args.config, "weights_bytes": wbytes,
           "hbm_bytes": hbm, "lanes": {}}
    for lanes in [int(x) for x in args.lanes.split(",")]:
        state = on_chip(jax.eval_shape(
            lambda: model.init_decode_state(lanes)))
        sbytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(state))
        vec = lambda d: sds((lanes,), d)  # noqa: E731
        dec = analyse(serve._lanes_block_fn(key, block, None, None),
                      params, state, vec(jnp.int32), vec(jnp.bool_),
                      vec(jnp.int32), vec(jnp.int32),
                      sds((lanes, 2), jnp.uint32), vec(jnp.float32),
                      vec(jnp.int32), vec(jnp.float32),
                      sds((block, lanes), jnp.bool_))
        grp = analyse(serve._prefill_group_fn(key), params,
                      sds((lanes, args.bucket), jnp.int32),
                      sds((lanes,), jnp.int32))
        one = analyse(serve._prefill_one_fn(key), params,
                      sds((args.bucket,), jnp.int32), sds((), jnp.int32))
        # the served block donates the state, so on the chip its new
        # state is written in place (the CPU backend here donates nothing)
        dec_extra = dec["temp_size_in_bytes"] + max(
            0, dec["output_size_in_bytes"] - sbytes)
        pre_extra = max(p["temp_size_in_bytes"] + p["output_size_in_bytes"]
                        for p in (grp, one))
        total = wbytes + sbytes + max(dec_extra, pre_extra)
        out["lanes"][lanes] = {
            "state_bytes": sbytes, "decode_block": dec,
            "prefill_group": grp, "prefill_one": one,
            "reckoned_peak_bytes": total,
            "fits": total < hbm}
        print(json.dumps({"lanes": lanes, **out["lanes"][lanes]}),
              flush=True)
    if args.reference:
        # the plain reference's prefill runs after the window on the same
        # chip, with the program's state freed
        from bench.reference import Reference
        for prec in ("f32", "int8"):
            ref = Reference(m, cfg["prune"], prec, prompt_pad=args.bucket)
            pre = analyse(ref.prefill, wshape,
                          sds((ref.P,), jnp.int32), sds((), jnp.int32))
            out[f"reference_{prec}"] = {"prefill": pre}
            print(json.dumps({f"reference_{prec}": out[f"reference_{prec}"]}),
                  flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
