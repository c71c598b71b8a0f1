"""Bound arguments of a verb call: values that belong to the call and
are not columns (a model's weights, k-means centers).

A bound value is a pytree. Its leaves are jit ARGUMENTS of the verb's
program, never constants of it, so new values of the same shapes run the
same executable. A leaf that is a `jax.Array` stays on the device: where
the call's schedule dispatches blocks to a device the leaf does not live
on, the leaf is copied there ONCE and the copy is found again by every
later call that binds the same array (the table below, keyed by the
leaf's identity and the device, dropped with the leaf). A host leaf
(numpy, a Python scalar) is placed once a call instead of once a block.

`place` runs inside the verb's plan under the span ``bindings.place``
and counts what it moves: ``bindings.bytes_placed`` (bytes copied to a
device, from the host or from another device) and ``bindings.leaves``
(leaves bound). A scoring loop over resident weights reads 0 bytes from
its second call on.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from ..utils import telemetry as _tele

__all__ = ["Bound", "place", "reset_state", "structure"]

# (id(leaf), device) -> (weakref to the leaf, its copy on the device)
_copies: Dict = {}
_lock = threading.Lock()


def reset_state() -> None:
    """Forget every copy (the test-isolation hook; a live process never
    needs it: a copy goes with the leaf it was made of)."""
    with _lock:
        _copies.clear()


def _copy_on(leaf: "jax.Array", device) -> "jax.Array":
    """`leaf` on `device`: itself where it lives there, else the one
    copy every call shares."""
    if leaf.sharding.device_set == {device}:
        return leaf
    key = (id(leaf), device)
    with _lock:
        hit = _copies.get(key)
        if hit is not None and hit[0]() is leaf:
            return hit[1]
    copy = jax.device_put(leaf, device)
    _tele.counter_inc("bindings.bytes_placed", float(leaf.nbytes))

    def drop(_ref, key=key):
        with _lock:
            _copies.pop(key, None)

    with _lock:
        _copies[key] = (weakref.ref(leaf, drop), copy)
    return copy


class Bound:
    """One bound value, placed: `on(device)` is the tree a block
    dispatched to `device` is fed (`device` None: an unscheduled call)."""

    __slots__ = ("treedef", "_by_device")

    def __init__(self, treedef, by_device):
        self.treedef = treedef
        self._by_device = by_device

    def on(self, device=None):
        leaves = self._by_device.get(device)
        if leaves is None:
            # a failover moved the block to a device the plan did not place
            # on: the one copy there, made and counted like any other
            source = next(iter(self._by_device.values()))
            leaves = [
                _copy_on(leaf, device)
                if device is not None and isinstance(leaf, jax.Array) else leaf
                for leaf in source
            ]
            self._by_device[device] = leaves
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def structure(bindings: Dict[str, object]) -> Sequence[str]:
    """The bound names with their tree structures: what the executor's
    cache keys a function's program by, beside the function."""
    return [
        f"{name}:{jax.tree_util.tree_structure(bindings[name])}"
        for name in sorted(bindings)
    ]


def place(
    bindings: Dict[str, object], devices: Optional[Sequence] = None,
    on_device: bool = True,
) -> Dict[str, Bound]:
    """Place every bound value on `devices` (a schedule's; None: leaves
    stay where they are). `on_device` False keeps host leaves on the host
    (an executor that owns its own device buffers)."""
    with _tele.span("bindings.place", values=len(bindings)):
        targets = list(devices) if devices else [None]
        out, n_leaves = {}, 0
        for name, value in bindings.items():
            leaves, treedef = jax.tree_util.tree_flatten(value)
            n_leaves += len(leaves)
            by_device = {}
            for dev in targets:
                placed = []
                for leaf in leaves:
                    if isinstance(leaf, jax.Array):
                        placed.append(leaf if dev is None else _copy_on(leaf, dev))
                        continue
                    leaf = np.asarray(leaf)
                    if on_device:
                        _tele.counter_inc("bindings.bytes_placed", float(leaf.nbytes))
                        leaf = jax.device_put(leaf, dev)
                    placed.append(leaf)
                by_device[dev] = placed
            out[name] = Bound(treedef, by_device)
        _tele.counter_inc("bindings.leaves", float(n_leaves))
        # the counter exists from the first call on, so a reader tells
        # "nothing moved" (0) from "no such counter" (a program without it)
        _tele.counter_inc("bindings.bytes_placed", 0.0)
        return out
