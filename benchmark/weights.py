"""Seeded weights, made on the device in one jitted call, in the types
they are served in. The layout is the program's own (``init_int8`` of the
model module gives the tree of shapes and dtypes, evaluated abstractly);
the values come from ``--seed`` here, because the program's maker takes
no seed and builds leaf by leaf."""

from __future__ import annotations

import functools
import math
from typing import Any


def int8_params(module, cfg, seed: int) -> Any:
    """A params tree shaped like ``module.init_int8(cfg)``: int8 matmul
    weights uniform over the int8 range with the per-channel scale that
    makes the dequantised weight ~N(0, 1/fan_in) in spread, norm gains of
    one, embeddings normal / sqrt(width). One ``jit``, one key."""
    import jax
    import jax.numpy as jnp

    abstract = jax.eval_shape(functools.partial(module.init_int8, cfg))

    def build(node, key, name):
        if isinstance(node, dict):
            if set(node) == {"q", "s"}:
                fan_in = node["q"].shape[-2]
                bits = jax.random.bits(key, node["q"].shape, jnp.uint8)
                return {"q": jax.lax.bitcast_convert_type(bits, jnp.int8),
                        "s": jnp.full(node["s"].shape,
                                      1.0 / (127.0 * math.sqrt(fan_in)),
                                      node["s"].dtype)}
            return {child: build(value, jax.random.fold_in(key, i), child)
                    for i, (child, value) in enumerate(sorted(node.items()))}
        if name.endswith("norm") or len(node.shape) < 2:
            return jnp.ones(node.shape, node.dtype)
        return (jax.random.normal(key, node.shape, jnp.float32)
                / math.sqrt(node.shape[-1])).astype(node.dtype)

    # one program for the whole tree: the described-chip compile (v5e,
    # PR 24) fuses every draw into its output, 0 bytes of temporaries.
    # The seed is an argument: as a constant it would make a new program,
    # and a compile of ~18 s, for every new seed
    make = jax.jit(lambda s: build(abstract, jax.random.key(s), ""))
    return make(jnp.uint32(seed % (2 ** 31 - 1)))


def tree_bytes(tree: Any) -> int:
    import jax

    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree))
