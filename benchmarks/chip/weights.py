"""Seeded weights, made on the device in one jitted call.

The program's checkpoint format is a pytree of named leaves (``embed``,
``scan_0/mix/wq``, ...; the leading axis of ``scan_*`` leaves stacks the
layers).  Both the program under test and the reference are handed the
same leaves, made here from ``--seed``: embeddings and routers
N(0, 0.02^2), projections N(0, 1/fan_in), and the norm scales 0 (the
program stores an RMSNorm gain ``g`` as ``g - 1``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

Leaf = Tuple[str, Tuple[int, ...], str]      # (path, shape, dtype)


def leaf_path(path) -> str:
    """``(DictKey('scan_0'), DictKey('mix'), ...)`` -> ``scan_0/mix/...``."""
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _std(path: str, cfg: Dict) -> float:
    name = path.rsplit("/", 1)[-1]
    if path == "embed" or name == "router":
        return 0.02
    if name == "scale":
        return 0.0
    if name == "wo" and "/mix/" in path:
        fan_in = cfg["num_attention_heads"] * cfg["head_dim"]
    elif name == "wo":
        fan_in = cfg["intermediate_size"]
    else:
        fan_in = cfg["hidden_size"]
    return float(fan_in) ** -0.5


def seed32(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    ss = np.random.SeedSequence([int(seed) & (2**63 - 1), 0x3E16])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def make(leaves: List[Leaf], cfg: Dict, seed: int) -> List[jax.Array]:
    """Every leaf, in ``leaves`` order, from one jitted call."""
    stds = [_std(p, cfg) for p, _, _ in leaves]

    @jax.jit
    def gen(key):
        out = []
        for i, ((_, shape, dtype), std) in enumerate(zip(leaves, stds)):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            out.append(x.astype(dtype))
        return out

    return gen(jax.random.key(seed32(seed)))


def by_path(leaves: List[Leaf], arrays) -> Dict[str, object]:
    return {p: a for (p, _, _), a in zip(leaves, arrays)}
