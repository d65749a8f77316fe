"""One module a model family, found by the name a configuration's file gives
under ``"family"`` (``manifest.load_plugin("families", name)``). Everything in
the harness that depends on the family is asked of that module, and nothing
else under ``benchmarks/`` imports ``ray_tpu.models``, ``ray_tpu.serve.llm``
or a family's names. What a family module gives (``families/llama.py`` is the
one there is; a new family is a new module here, with its plain reference
beside it, and edits no file):

every kind
    ``program_config(cfg)``: the program's configuration object from the
    configuration file's dict (the source's key names). A tool that sizes
    another depth or pool changes the dict's keys, not the object.
    ``init_weights(config, key)``: the program's parameter pytree, traced
    under ``jit``; ``make_weights(config, seed)``: one jitted call from
    ``harness.weights.seed_key(seed)``, in the type the weights are served in.
    ``reference_logits(params, tokens[S], cfg, quant=None)``,
    ``reference_loss(params, tokens[R, S], targets[R, S], cfg, quant=None)``,
    ``make_gap_fn(cfg, quant=None)``, ``make_greedy_fn(cfg, quant=None)``:
    the plain reference (``harness/reference.py`` has what they share), which
    imports nothing of the program.

``"kind": "serve"``
    ``make_engine(config, params, deployment)``: the engine for a
    ``deployment`` block. It gives ``generate(tokens=, max_tokens=,
    eos_token=, timeout=)``, ``generate_stream(...)`` with the same arguments
    (token records, then a done record with ``latency_s`` and ``hops``),
    ``stop()`` and ``stats()`` with the fields ``ray_tpu.serve.llm.LLMEngine``
    documents, under those names: the readers of engine counters read them.
    ``set_weights(engine, params)``: new weights into an idle engine (tools).
    ``serve_programs(config, deployment)``: for ``tools/size_memory.py``, the
    abstract weights, the abstract state a replica keeps beside them, and each
    program the engine runs with its abstract arguments.
    ``DECODE_MODULE``, ``PREFILL_MODULE``: regular expressions over the names
    its two programs have in a profile, and ``PREFILL_ROWS_FROM``: where a
    prefill call holds several rows, the operation that tells how many
    (``readers/module_time.py`` ``rows_from``), else None. All three are
    REQUIRED: ``decode_device_per_step`` and ``prefill_device_per_call`` are
    one entry each for every family and name these
    (``manifest.resolve_params`` raises for a name a family lacks): a new
    family states its own and adds its cell to their ``workloads``.

``"kind": "train"``
    ``loss(params, tokens, targets, config)``: the program's loss, whose
    gradient ``correct`` compares with the reference's.
    ``make_train_step(config, optimizer, mesh=None)``: the compiled step
    ``(state, tokens, targets) -> (state, {"loss", "grad_norm", "step"})``
    over a ``ray_tpu.train.step.TrainState``; ``state_shardings(config,
    optimizer, mesh)`` for a state made in its shards.
    ``train_flops_per_token(cfg, seq)``: the FLOPs a trained token needs
    (forward and backward, no recompute), which ``train_mfu`` divides by.
"""
