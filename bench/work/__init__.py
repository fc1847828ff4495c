"""Work a layer needs, counted from the configuration and the batch.

Each module gives ``calls(cfg, lanes)``: the ``(ops, bytes)`` of each
kernel call of one engine step whose live lanes are ``lanes``, a list of
``(start, n)`` -- ``n`` new tokens after ``start`` resident ones.  Only
live tokens count, never padding, so the counts do not depend on how
the layer is implemented.  ``cfg`` is a configuration file's dict; the
counts are its architecture module's (``WORK``, ``arch/``), and a kernel
the architecture does not call gives none.
"""

from bench import spec


def kernel_calls(kernel: str, cfg: dict, lanes) -> list:
    """The calls of ``kernel`` that ``cfg``'s architecture counts."""
    fn = spec.architecture(cfg).WORK.get(kernel)
    return fn(cfg, lanes) if fn else []
