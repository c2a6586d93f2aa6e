"""The engine seam.

This is the exact boundary identified in SURVEY.md §3.3: the reference's
per-worker ``StockfishStub::go(Position) -> PositionResponse``
(src/stockfish.rs:45-53) behind which the whole engine implementation can
be swapped. Engines here are:

* ``mock``     — deterministic instant engine for tests;
* ``uci``      — drives an external UCI engine subprocess, reproducing the
                 reference's process-per-worker model (correctness oracle);
* ``tpu-nnue`` — the native C++ search core with leaf evaluations batched
                 onto TPU (the point of this framework).
"""

from __future__ import annotations

import abc
from typing import Dict, Optional

from fishnet_tpu.ipc import EngineError, Position, PositionResponse
from fishnet_tpu.protocol.types import EngineFlavor

__all__ = ["Engine", "EngineFactory", "EngineError"]


class Engine(abc.ABC):
    """One engine instance, owned by one worker at a time."""

    @abc.abstractmethod
    async def go(self, position: Position) -> PositionResponse:
        """Search one position. Raises EngineError on any engine failure
        (the worker will discard this engine and restart with backoff,
        reference src/main.rs:335-341)."""

    @abc.abstractmethod
    async def close(self) -> None:
        """Tear down (kill subprocess / release slots). Idempotent."""


class EngineFactory(abc.ABC):
    """Creates engines per flavor. Workers cache one engine per flavor
    (reference src/main.rs:266-269)."""

    @abc.abstractmethod
    async def create(self, flavor: EngineFlavor) -> Engine:
        ...

    async def prepare(self) -> None:
        """Bring any shared backend up before the client acquires work:
        build it and compile what it will run. Called once at start-up;
        a failure here is a start-up error (the process exits), never a
        job's. Engines with nothing to warm keep this no-op."""
        return None

    def close(self) -> None:
        """Tear down any shared backend (search service driver threads).
        Called once at client shutdown; a daemon thread left inside
        native/JAX code at interpreter exit aborts the process."""
        return None
