"""Closed-loop traffic from a data file and a seed.

A traffic file (``traffic/<name>.json``) gives the number of clients,
the prompt and output length distributions and the pool size the mix
needs.  Each client sends its next request as soon as the previous one
finished (closed loop: callers that each wait for a reply).

Every seed gets the same work: the lengths of each client's requests
are drawn from the file's own ``size_seed``, and the run's seed draws
every prompt's tokens (and the model's weights).  So two seeds differ in
what is computed, not in how much work a window holds or in when it
arrives.  Each request is drawn from its own stream, so it does not
depend on the order in which others are drawn.

Every client's first request is *in flight* when the run starts: part
of its output is already done and joins the prompt.  Client ``i`` of
``n`` has ``(i + u) / n`` of its output left (``u`` uniform), so the phases of
the clients are spread evenly, as in a loop that has run for a while:
the run starts with that loop's spread of context lengths and its rate
of completions, not with every client at its first token.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Lengths:
    """A length distribution: ``uniform`` on [min, max], or
    ``lognormal`` with the given median and sigma, clipped to
    [min, max]."""
    dist: str
    min: int
    max: int
    median: float = 0.0
    sigma: float = 0.0

    def __post_init__(self):
        if self.dist not in ("uniform", "lognormal"):
            raise ValueError(f"unknown length distribution {self.dist!r}")
        if not 1 <= self.min <= self.max:
            raise ValueError(f"bad length range [{self.min}, {self.max}]")
        if self.dist == "lognormal" and not (self.median > 0
                                             and self.sigma > 0):
            raise ValueError("lognormal lengths need median > 0, sigma > 0")

    def draw(self, rng: np.random.Generator) -> int:
        if self.dist == "uniform":
            return int(rng.integers(self.min, self.max + 1))
        x = math.exp(math.log(self.median) + self.sigma * rng.standard_normal())
        return int(min(max(round(x), self.min), self.max))


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One traffic mix (see the module docstring)."""
    clients: int
    prompt: Lengths
    output: Lengths
    pool_blocks: int
    size_seed: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "Traffic":
        if d.get("loop", "closed") != "closed":
            raise ValueError(f"only closed-loop traffic is generated, "
                             f"not {d.get('loop')!r}")
        if int(d["clients"]) < 1:
            raise ValueError("traffic needs at least one client")
        return cls(clients=int(d["clients"]),
                   prompt=Lengths(**d["prompt_tokens"]),
                   output=Lengths(**d["output_tokens"]),
                   pool_blocks=int(d["pool_blocks"]),
                   size_seed=int(d.get("size_seed", 0)))

    @property
    def max_tokens(self) -> int:
        """Longest request: prompt plus output."""
        return self.prompt.max + self.output.max


@dataclasses.dataclass
class Draw:
    """One request as a client sends it."""
    prompt: np.ndarray          # (s,) int32 token ids
    max_new_tokens: int


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(w) % 2**64 for w in words]))


def lengths(traffic: Traffic, client: int, index: int) -> tuple:
    """(prompt, output) lengths of request ``index`` of ``client``: the
    same for every run seed.  Request 0 is in flight: its emitted share
    of the output has joined the prompt."""
    rng = _rng(traffic.size_seed, client, index)
    p = traffic.prompt.draw(rng)
    o = traffic.output.draw(rng)
    if index == 0:
        left = max(1, math.ceil(o * (client + rng.random())
                                / traffic.clients))
        p, o = p + o - left, left
    return p, o


def draw(traffic: Traffic, seed: int, vocab: int, client: int,
         index: int) -> Draw:
    """Request ``index`` of ``client`` under run ``seed``."""
    p, o = lengths(traffic, client, index)
    prompt = _rng(seed, client, index).integers(0, vocab, p, dtype=np.int32)
    return Draw(prompt, o)
