"""The benchmark's general generator: what every configuration kind shares.

A configuration kind (``bench/kinds/<kind>.py``) makes its own jobs from
its configuration, the traffic mix and ``--seed`` with ``rng_for``, and
places what the program's builders made with ``placed``.  A serving mix
draws each request's presentation length from ``t_steps_choices`` with
``serve_t_steps``, and ``serve_entry`` says which request each client
sends.  Every seed gets the same sizes, modes and multiset of
presentation lengths; only the data and the order change with the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rng_for(seed: int, *key: int) -> np.random.Generator:
    """A generator for one job of one run: any non-negative ``seed``,
    however large, and a job index."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *key]))


def placed(build, *args):
    """Run one of the program's builders on the host's CPU backend, then
    place what it built on the default device, as arrays the program may
    still move (a server shards them over its mesh).  The builders set up
    state unit by unit, thousands of small operations that cost a dispatch
    each on an accelerator and next to nothing on the host."""
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        cfg, states, pending, *rest = build(*args)
    states, pending = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)),
                                   (states, pending))
    return (cfg, states, pending, *rest)


def serve_t_steps(traffic: dict, seed: int) -> list[int]:
    """Presentation length of each pool entry: every seed gets the same
    multiset (each choice equally often), in a seeded order."""
    choices = traffic["t_steps_choices"]
    n = traffic["pool"]
    assert n % len(choices) == 0, "pool must hold each length equally often"
    ts = np.repeat(np.asarray(choices), n // len(choices))
    return [int(t) for t in rng_for(seed, 1 << 30).permutation(ts)]


def serve_entry(t_steps: list[int], client: int, flush: int) -> int:
    """The pool entry ``client`` sends in flush ``flush`` of a closed loop
    over a pool of presentation lengths ``t_steps``: client ``c`` sends
    requests of the ``c % L``-th of the ``L`` lengths, the pool's entries
    of that length in turn, so every flush carries the same mix of lengths,
    and with it the same simulated work, on every seed."""
    lengths = sorted(set(t_steps))
    same = [e for e, t in enumerate(t_steps)
            if t == lengths[client % len(lengths)]]
    return same[(client // len(lengths) + flush) % len(same)]
