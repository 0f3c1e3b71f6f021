"""Counters of things a process should do once, such as putting a constant
on its device: shipped code bumps one with :func:`add`, and a node-loader's
heartbeat carries :func:`snapshot` beside its compile counts, so a count
that moves once the node is warm shows on the host's ``/metrics``.

Per process, always on, stdlib only.  The counter names and what reads
them are listed in ARCHITECTURE.md "Observability".
"""

from __future__ import annotations

import threading

__all__ = ["add", "snapshot"]

_lock = threading.Lock()
_counts: dict[str, int] = {}


def add(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` (it starts at 0)."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + n


def snapshot() -> dict[str, int]:
    """Every counter of this process, by name."""
    with _lock:
        return dict(_counts)
