"""Determinism lint for the aggregation fold and kernel modules.

The fig5 claim is *bitwise* equality across transports and arrival
orders, and the fold defends it with canonical node order, fp64
accumulation on the host, and on the chip a compensated fp32 fold whose
products are all exact (kernels/agg_reduce.py docstring).  These rules
flag the patterns that silently break it:

- ``det-set-iter``: iterating a ``set`` (arrival/hash order) in an
  aggregation module — node ids must be sorted before folding;
- ``det-entropy``: ``time.*`` / ``random.*`` / legacy global
  ``np.random.*`` in fold paths (seeded ``np.random.default_rng`` and
  explicit ``Generator``/``SeedSequence`` plumbing are fine);
- ``det-float-accum``: builtin ``sum()``/``math.fsum()`` inside a traced
  (jnp/lax/pallas-using) function — Python-float reduction order is
  invisible to the fold's pairing contract;
- ``det-fma-split``: Veltkamp's split ``x * (2**s + 1)`` in a kernel
  module — it is exact only if the product is rounded before the
  subtraction, and XLA contracts multiply-adds into FMAs per fusion, so
  the split halves (and every error term built on them) change with the
  fusion.  Split with a bit mask (``agg_reduce._split``) instead.
"""
from __future__ import annotations

import ast
from typing import Iterable

from repro.analysis.core import Check, Finding, Module

#: np.random attributes that are deterministic-by-construction plumbing
_NP_RANDOM_OK = {"default_rng", "Generator", "SeedSequence", "Philox",
                 "PCG64", "bit_generator", "BitGenerator"}

#: fold-path modules for the accumulation-order rules
_FOLD_BASENAMES = {"agg_kernels.py", "strategy.py", "legacy.py", "flat.py"}


def _attr_chain(node: ast.AST):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _uses_tracing(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and node.id in ("jnp", "lax", "pl"):
            return True
        if isinstance(node, ast.Attribute):
            chain = _attr_chain(node)
            if chain and chain[0] == "jax":
                return True
    return False


class DeterminismCheck(Check):
    rules = ("det-set-iter", "det-entropy", "det-float-accum",
             "det-fma-split")

    def scope(self, mod: Module) -> bool:
        return ("fl" in mod.segments or "kernels" in mod.segments
                or mod.basename == "sharding.py")

    def visit(self, mod: Module) -> Iterable[Finding]:
        yield from self._set_iter(mod)
        yield from self._entropy(mod)
        if mod.basename in _FOLD_BASENAMES or "kernels" in mod.segments:
            yield from self._float_accum(mod)
            yield from self._fma_split(mod)

    # ------------------------------------------------------------------
    def _set_iter(self, mod: Module) -> Iterable[Finding]:
        def iter_exprs():
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.For, ast.AsyncFor)):
                    yield node.iter
                elif isinstance(node, (ast.ListComp, ast.SetComp,
                                       ast.DictComp, ast.GeneratorExp)):
                    for gen in node.generators:
                        yield gen.iter
        for it in iter_exprs():
            bad = isinstance(it, (ast.Set, ast.SetComp)) or (
                isinstance(it, ast.Call)
                and isinstance(it.func, ast.Name)
                and it.func.id in ("set", "frozenset"))
            if bad:
                yield Finding(
                    "det-set-iter", mod.path, it.lineno, it.col_offset,
                    "iterating a set in an aggregation module: hash "
                    "order leaks into the fold — sort first "
                    "(sorted(...))")

    def _entropy(self, mod: Module) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if not chain:
                continue
            if chain[0] == "time" and len(chain) == 2:
                yield Finding(
                    "det-entropy", mod.path, node.lineno, node.col_offset,
                    f"time.{chain[1]}() in a fold path: aggregation "
                    "must not depend on the clock")
            elif chain[0] == "random" and len(chain) == 2:
                yield Finding(
                    "det-entropy", mod.path, node.lineno, node.col_offset,
                    f"random.{chain[1]}() uses ambient global state; "
                    "thread a seeded np.random.Generator instead")
            elif (len(chain) >= 3 and chain[0] in ("np", "numpy")
                    and chain[1] == "random"
                    and chain[2] not in _NP_RANDOM_OK):
                yield Finding(
                    "det-entropy", mod.path, node.lineno, node.col_offset,
                    f"legacy global np.random.{chain[2]}() is ambient "
                    "state; use np.random.default_rng(seed)")

    def _float_accum(self, mod: Module) -> Iterable[Finding]:
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not _uses_tracing(fn):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                is_sum = isinstance(f, ast.Name) and f.id == "sum"
                chain = _attr_chain(f)
                is_fsum = chain == ("math", "fsum")
                if is_sum or is_fsum:
                    yield Finding(
                        "det-float-accum", mod.path, node.lineno,
                        node.col_offset,
                        "builtin sum()/math.fsum() inside a traced "
                        "function hides the fold's add order; write the "
                        "adds out (the compensated pair's order) or use "
                        "jnp.sum")

    def _fma_split(self, mod: Module) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Mult)):
                continue
            if any(_veltkamp_constant(side)
                   for side in (node.left, node.right)):
                yield Finding(
                    "det-fma-split", mod.path, node.lineno,
                    node.col_offset,
                    "Veltkamp split x * (2**s + 1): FMA contraction "
                    "(applied per XLA fusion) breaks its exactness — "
                    "split with a bit mask (agg_reduce._split)")


def _const_int(node: ast.AST):
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
        v = node.value
        return int(v) if float(v).is_integer() else None
    if isinstance(node, ast.BinOp):
        a, b = _const_int(node.left), _const_int(node.right)
        if a is None or b is None:
            return None
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Pow) and 0 <= b < 64:
            return a ** b
        if isinstance(node.op, ast.LShift) and 0 <= b < 64:
            return a << b
    return None


def _veltkamp_constant(node: ast.AST) -> bool:
    """``2**s + 1`` for a split width ``s >= 10`` (1025, 4097, 2**27+1 ...),
    written as a literal or as constant arithmetic."""
    v = _const_int(node)
    if v is None or v < 1025:
        return False
    m = v - 1
    return m & (m - 1) == 0
