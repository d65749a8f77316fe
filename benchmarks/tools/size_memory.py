"""Chip-less compiles of a configuration's programs at real widths, for a v5e
that is described and not attached, with ``memory_analysis()`` of each. The
configuration's family (``families/<name>.py``) names the programs.

    JAX_PLATFORMS=cpu python -m benchmarks.tools.size_memory serve 769 1537 3073
    JAX_PLATFORMS=cpu python -m benchmarks.tools.size_memory train 4 5 6

The first argument is a configuration of ``BENCHMARK.json`` by name, or a
``kind``, which stands for the manifest's first configuration of that kind.
The numbers are values of the key that sizes that kind: ``total_pages`` of a
``serve`` deployment, ``num_hidden_layers`` of a ``train`` configuration.

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


def _nbytes(tree):
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _config_file(manifest, which):
    """``which``: a configuration's name, or a kind (the first of that kind)."""
    from benchmarks.harness.weights import load_config_file

    for c in manifest["configs"]:
        cfg = load_config_file(os.path.join(ROOT, c["file"]))
        if which in (c["name"], cfg["kind"]):
            return cfg
    raise SystemExit(f"no configuration named {which!r} or of that kind")


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.manifest import family_of, load_manifest

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    cfg = _config_file(load_manifest(), argv[0])
    family, dep = family_of(cfg), cfg["deployment"]
    values = [int(a) for a in argv[1:]]
    if cfg["kind"] == "serve":
        config = family.program_config(cfg)
        for k, pages in enumerate(values):
            sized = family.serve_programs(config, {**dep, "total_pages": pages})
            if k == 0:
                print(json.dumps({"weights_bytes": _nbytes(sized["weights"])}))
            out = {"total_pages": pages, "pool_bytes": _nbytes(sized["state"])}
            for name, fn, args in sized["programs"]:
                out[name] = _mem(fn.lower(*on(args)).compile())
            print(json.dumps(out), flush=True)
    elif cfg["kind"] == "train":
        from ray_tpu.train.step import default_optimizer

        from benchmarks.runners.train import init_state

        for layers in values:
            config = family.program_config({**cfg, "num_hidden_layers": layers})
            opt = default_optimizer(warmup_steps=10, total_steps=1000)
            state = on(jax.eval_shape(
                lambda k: init_state(family, config, opt, k), jax.random.key(0)))
            toks = jax.ShapeDtypeStruct(
                (dep["batch_rows"], dep["max_seq_len"]), jnp.int32, sharding=one)
            step = family.make_train_step(config, opt)
            print(json.dumps({
                "layers": layers,
                "params": sum(x.size for x in jax.tree.leaves(state.params)),
                "state_bytes": _nbytes(state),
                "step": _mem(step.lower(state, toks, toks).compile())}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
