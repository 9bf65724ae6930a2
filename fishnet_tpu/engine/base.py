"""Engine interface: chunk batches and position-level sessions.

The reference keeps Stockfish subprocesses behind exactly the
`go_multiple` shape (reference: src/stockfish.rs:36-48
`StockfishStub::go_multiple`); here it is the seam between the client
framework and the backends (TPU batch engine, UCI subprocess,
pure-Python fallback, supervised child host).

Since the serving round the protocol also carries `submit()`: one
position with its own deadline and priority, answered by one
PositionResponse (engine/session.py `PositionRequest`). Frontends that
hold positions rather than fishnet chunks — the HTTP server
(fishnet_tpu/serve/), bench closed-loop clients — speak this surface;
backends conform via the `ChunkSubmit` mixin (engine/session.py), which
wraps a request as a one-position chunk, so every backend that can run
a chunk can serve position traffic too.
"""
from __future__ import annotations

import os
from typing import TYPE_CHECKING, List, Protocol

from ..client.ipc import Chunk, PositionResponse

if TYPE_CHECKING:  # circular at runtime: session.py builds Chunks
    from .session import PositionRequest


class EngineError(Exception):
    """Engine died or misbehaved; the worker drops and respawns it with
    backoff (reference: src/main.rs:330-336)."""


class NoAcceleratorError(EngineError):
    """`--backend tpu` came up on XLA:CPU without being asked to. No
    retry cures it, and serving from the CPU under the TPU's name would
    hide the device — the boot fails instead."""


# exit status of an engine host (engine/host.py) whose boot was refused
# for this reason; the supervisor turns it back into NoAcceleratorError
EXIT_NO_ACCELERATOR = 3


def cpu_asked_for() -> bool:
    """True when the environment makes the CPU JAX's default platform
    (JAX_PLATFORMS, the standard variable, with cpu named first): tests
    and CPU tools set it, and then a CPU backend is what was asked for,
    not a fallback. `tpu,cpu` asks for the TPU: JAX fails at start-up
    where it cannot have it."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def require_accelerator(platform: str) -> None:
    """Refuse an un-asked-for CPU backend (`platform` is what
    jax.default_backend() reports in the process that owns the engine)."""
    if platform == "cpu" and not cpu_asked_for():
        raise NoAcceleratorError(
            "backend 'tpu' found no accelerator: JAX's default backend is "
            "cpu. Refusing to serve from XLA:CPU under the TPU's name; set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose."
        )


class Engine(Protocol):
    async def go_multiple(self, chunk: Chunk) -> List[PositionResponse]:
        """Analyse every position of the chunk, in order."""
        ...

    async def submit(self, request: "PositionRequest") -> PositionResponse:
        """Analyse one position-level request (engine/session.py); the
        deadline/priority ride the request instead of a chunk."""
        ...

    async def close(self) -> None:
        ...


class EngineFactory(Protocol):
    def __call__(self, flavor) -> Engine:
        ...
