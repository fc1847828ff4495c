"""The whole step: every kernel call the architecture module counts
(its linears, LM head and attention), in the order the step runs them."""

from bench import spec


def calls(cfg: dict, lanes) -> list:
    return [c for fn in spec.architecture(cfg).WORK.values()
            for c in fn(cfg, lanes)]
