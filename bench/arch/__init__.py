"""Architecture modules: all that the benchmark knows of a model's
architecture, one module to an architecture.

A configuration file names its module with the key ``"arch"``, resolved
by ``spec.architecture`` to ``arch/<name>.py`` (or, for architectures
that only the tests serve, ``tests/arch/<name>.py``); a file without the
key means ``dense_decoder``.  A module gives:

- ``model_fields(config) -> dict``: the program's ``ModelConfig`` fields
  that the published keys of ``config`` set, over the program's own
  configuration named by ``repo_config``;
- ``reference_weights(flat) -> dict``: the reference's weights from the
  seeded tree's ``{path: array}`` view (``weights.plain``), nothing
  computed;
- ``logits_at(weights, tokens, idx, cfg)``: the plain float32 reference
  forward (built from ``reference.py``), logits ``(M, vocab)`` at
  positions ``idx`` of ``tokens``, with ``cfg`` the dict of ``static``;
- ``static(config) -> tuple``: the sizes ``logits_at`` reads, hashable
  for ``jax.jit``;
- ``WORK``: ``{kernel: calls(cfg, lanes)}``, the ``(ops, bytes)`` of
  each call of that kernel in one engine step whose live lanes are
  ``lanes`` (see ``work/__init__.py``), in the order the step runs them.

A new architecture is a new module here; nothing outside it names the
layers of one.
"""
