"""Served architectures, one module each: ``bench/archs/<arch>.py``,
named by the ``"arch"`` key of a served configuration file and loaded
by ``bench.serving.load_arch``.

A module is a set of plain module-level names, with no base class and
no registry:

- ``Spec``, with ``Spec.from_config(config)``: the sizes read from the
  configuration file, among them ``vocab_size``;
- ``make_weights(spec, seed)``: every weight, drawn from the seed on the
  device in one jitted call, in the type it is served in;
- ``logits``, ``gaps`` and ``gaps_program(spec, fp8)``: the plain float32
  reference, which imports nothing of the program, and its float8
  control;
- ``program_config(spec, serve)`` and ``program_params(spec, weights)``:
  the program's ``ModelConfig`` and the ``init_model`` parameter tree
  holding those weights;
- ``prefill_flops(spec, s)``, ``decode_bytes(spec, kv_rows)`` and
  ``kv_bytes_per_token(spec)``: what the per-layer readers divide by;
- ``small(config)``: the configuration cut to a size a CPU test runs.
"""
