"""Attributes that import their module on first use.

``flax`` and ``optax`` cost about half a second each to import (0.52 s
and 0.63 s on the v5e host; PR 26) and a train step needs neither: the
flax ``nn.Module`` wrappers of the functional cores live in sibling
``*_flax`` modules, and the packages forward their names through a
module ``__getattr__`` (PEP 562) built here, so ``import apex_tpu`` and
the trainers' own imports stay off both.
"""
import importlib
from typing import Callable, Iterable


def forward(package: str, target: str, names: Iterable[str]) -> Callable:
    """A module ``__getattr__`` serving ``names`` from ``target`` (a module
    path relative to ``package``), imported at the first lookup."""
    names = frozenset(names)

    def __getattr__(name):
        if name in names:
            return getattr(importlib.import_module(target, package), name)
        raise AttributeError(f"module {package!r} has no attribute {name!r}")

    return __getattr__
