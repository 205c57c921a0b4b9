"""Everything the benchmark knows of a configuration's shape, one module per
model: ``bench/models/<model>.py``, found by the configuration file's
``model`` key, as its plain reference (``bench/reference/<model>.py``) and
its FLOP count (``bench/flops/<model>.py``) are. A configuration whose layers
differ from every model here needs those three files and no edit elsewhere.

A model module gives:

- ``spec(cfg_file) -> dict``: the sizes that the reference, the FLOP count
  and the traffic read. It holds ``"V"``, the vocabulary the rows are drawn
  from; the rest is the model's own.
- ``make_weights(seed, spec, device) -> {name: bf16 array}``: the frozen
  base from the seed, made on ``device``. The program and the reference
  both train on these arrays.
- ``program_config(cfg_file) -> ModelConfig``: the program's configuration
  with every size taken from the file; raises where the program would run
  something else than the file states.
- ``base_tree(weights, cfg)``: the program's base-parameter tree filled
  with ``weights``.
- ``adapter_norms(tree) -> {name: {"a"|"b": array (layers, slots)}}``: the
  Frobenius norm of each layer's matrix of every adapter slot, from the
  program's packed adapter tree. The names are those of the reference's
  ``grads`` and ``update`` readings; two names may count different layers.

The reference imports a model module for its shapes, so a model module
imports the program (``repro``) only inside the functions that build the
program's side.
"""
