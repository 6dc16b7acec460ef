"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted on
the CPU backend (tests and smoke runs), and nowhere else."""
from __future__ import annotations

import functools
import logging

import jax

log = logging.getLogger(__name__)


@functools.lru_cache(maxsize=None)
def _announce_interpret(backend: str) -> None:
    log.warning("Pallas kernels run in interpret mode on the %s backend",
                backend)


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel entry point's ``interpret`` flag: the caller's explicit
    choice, else the backend's answer — False on a TPU, True on the CPU
    backend (logged once), an error on any other backend."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        _announce_interpret(backend)
        return True
    raise RuntimeError(f"no Pallas TPU kernel path on backend {backend!r}")
