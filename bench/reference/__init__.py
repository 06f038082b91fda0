"""Family modules: the one place that knows a model family.

A configuration file names its family module by its ``reference`` key;
``bench.core.registry.family`` loads ``bench/reference/<reference>.py``
of the checkout by path, so a family is added by adding its file.
``bench/core`` reads the common parts of a configuration (``arch``,
``reference``, ``serving``, ``rehearsal``, ``quantization`` and the
descriptive keys) and reaches everything else through these names:

- ``KEYS``: the published top-level keys the module reads. Every other
  top-level key of the file is an error (``bench.core.config``), so a
  file cannot state a size that nothing uses.
- ``Sizes``: a frozen, hashable dataclass with at least ``vocab`` (the
  traffic draws token ids below it) and ``context`` (the longest
  position the model takes).
- ``sizes(top, quantization) -> Sizes``: from the file's top-level keys
  and its ``quantization`` group (``{}`` where the file has none).
- ``make_weights(sizes, seed, padded_vocab)``: the weight tree the
  program consumes, made on the device from the seed in one jitted call.
- ``program_config(sizes, base)``: the program's ``ModelConfig``, every
  field that shapes the computation taken from ``sizes``; ``base`` is the
  program's own configuration of the file's ``arch``.
- ``span_flops(sizes, start, n, logits_rows)`` and
  ``decode_token_flops(sizes, position)``: the FLOPs the equations need
  for live rows only.
- ``logits_at(sizes, mm)``: the plain reference, a jitted
  ``f(params, tokens, rows) -> (rows, vocab)`` float32 logits; ``mm="fp8"``
  is the lower-precision control.

A family whose FFNs the program can stream through its ``weight_stream``
kernel also defines ``streamed_matmuls(sizes)``: ``(k, n, bits)`` of each
packed matrix a streamed layer multiplies by, or ``()``.
"""
