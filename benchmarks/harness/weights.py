"""Load the program's parameters with the benchmark's own weights: made
on the device, from the seed, in the type they are stored in, by ONE
jitted call whose outputs land at each parameter's sharding. The recipe
for a leaf (``references/gpt.py::leaf``) is the reference's, so program
and reference hold the same numbers and neither takes them from the
other.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..references.gpt import key_data, leaf, name_id

# a program parameter is one leaf, or a stack of same-shaped leaves
Names = Union[str, Sequence[str]]


def seed_key(seed: int):
    return jax.random.wrap_key_data(jnp.asarray(key_data(seed)))


def generator(entries: List[Tuple[Names, Tuple]], dtype, shardings=None
              ) -> Callable:
    """jit(key) -> tuple of arrays, one per entry (names, spec)."""
    def gen(key):
        out = []
        for names, spec in entries:
            if isinstance(names, str):
                out.append(leaf(key, name_id(names), spec, dtype))
            else:
                nids = jnp.asarray([name_id(n) for n in names], jnp.int32)
                out.append(jax.vmap(
                    lambda nid: leaf(key, nid, spec, dtype))(nids))
        return tuple(out)
    return jax.jit(gen, out_shardings=shardings)


def load(params: Sequence, names_of: Dict[str, Names], table: Dict,
         seed: int, dtype, sharding_of=None) -> Callable:
    """Set ``p._value`` of every (program name, parameter) pair in
    ``params``. Returns the generator (call it with ``seed_key(seed)``
    to get the same values again, e.g. to measure how far a step moved
    them)."""
    entries = []
    shardings = []
    for pname, p in params:
        names = names_of[pname]
        first = names if isinstance(names, str) else names[0]
        spec = table[first]
        shape = spec[0] if isinstance(names, str) \
            else (len(names),) + tuple(spec[0])
        have = tuple(p._value.shape)
        if tuple(shape) != have:
            raise ValueError(f"{pname}: the program holds {have}, the "
                             f"reference's table says {tuple(shape)}")
        entries.append((names, spec))
        shardings.append(sharding_of(p) if sharding_of else None)
    gen = generator(entries, jnp.dtype(dtype),
                    tuple(shardings) if sharding_of else None)
    for (_, p), v in zip(params, gen(seed_key(seed))):
        p._value = v
    return gen
