"""Chip-less compiles of the cells' programs at real widths, for a v5e that
is described and not attached, with ``memory_analysis()`` of each.

    JAX_PLATFORMS=cpu python -m benchmarks.tools.size_memory serve 769 2049 3073
    JAX_PLATFORMS=cpu python -m benchmarks.tools.size_memory train 4 5 6

Nothing runs, so nothing printed here is a device number: the figures size
``total_pages`` and the train depth before chip time is spent, and are
written into the configuration files' ``hbm_reckoning``.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def _mem(compiled):
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "aliased": m.alias_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "code": m.generated_code_size_in_bytes}


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.weights import (
        init_weights, llama_config_from_file, load_config_file)

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    kind = argv[0]
    if kind == "serve":
        from ray_tpu.models import paged_decode as pd

        cfg = load_config_file(os.path.join(
            ROOT, "benchmarks/configs/mistral-7b-v0.3-serve.json"))
        dep = cfg["deployment"]
        config = llama_config_from_file(cfg)
        params = on(jax.eval_shape(lambda k: init_weights(config, k),
                                   jax.random.key(0)))
        weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
        print(json.dumps({"weights_bytes": weights}))
        slots, page, chunk = dep["num_slots"], dep["page_size"], dep["decode_chunk"]
        table_pages = dep["max_seq_len"] // page
        for pages in [int(a) for a in argv[1:]]:
            cache = on(jax.eval_shape(
                lambda: pd.init_paged_cache(config, pages, page)))
            pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
            ints = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
            active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
            table = jax.ShapeDtypeStruct((slots, table_pages), jnp.int32, sharding=one)
            key = on(jax.eval_shape(lambda: jax.random.key(0)))
            decode = pd.make_paged_decode_fn(config, chunk, page, use_kernel=True)
            out = {"total_pages": pages, "pool_bytes": pool,
                   "decode": _mem(decode.lower(params, cache, ints, ints, active,
                                               table, key).compile())}
            prefill = pd.make_paged_prefill_fn(config, page)
            for bucket in dep["prefill_buckets"]:
                toks = jax.ShapeDtypeStruct((8, bucket), jnp.int32, sharding=one)
                pgs = jax.ShapeDtypeStruct((8, bucket // page), jnp.int32, sharding=one)
                lens = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one)
                out[f"prefill_{bucket}"] = _mem(
                    prefill.lower(params, cache, toks, pgs, lens).compile())
            print(json.dumps(out), flush=True)
    elif kind == "train":
        from ray_tpu.train.step import default_optimizer, make_train_step

        from benchmarks.runners.train import init_state

        cfg = load_config_file(os.path.join(
            ROOT, "benchmarks/configs/mistral-7b-v0.3-train.json"))
        dep = cfg["deployment"]
        for layers in [int(a) for a in argv[1:]]:
            config = llama_config_from_file(cfg, num_layers=layers)
            opt = default_optimizer(warmup_steps=10, total_steps=1000)
            state = on(jax.eval_shape(lambda k: init_state(config, opt, k),
                                      jax.random.key(0)))
            nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
            toks = jax.ShapeDtypeStruct(
                (dep["batch_rows"], dep["max_seq_len"]), jnp.int32, sharding=one)
            step = make_train_step(config, opt)
            print(json.dumps({
                "layers": layers, "params": config.num_params,
                "state_bytes": nbytes,
                "step": _mem(step.lower(state, toks, toks).compile())}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
