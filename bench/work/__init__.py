"""Work a layer needs, counted from the configuration and the batch.

Each module gives ``calls(cfg, lanes)``: the ``(ops, bytes)`` of each
kernel call of one engine step whose live lanes are ``lanes``, a list of
``(start, n)`` -- ``n`` new tokens after ``start`` resident ones.  Only
live tokens count, never padding, so the counts do not depend on how
the layer is implemented.  ``cfg`` is a configuration file's dict.
"""
