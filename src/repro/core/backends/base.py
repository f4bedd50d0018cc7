"""Backend protocol + registry (the paper's pluggable Phase-4 seam).

A *backend* owns everything after lowering: it consumes the typed RGIR
stream and produces an executor object.  The contract (``ExecutorLike``)
is intentionally small so backends can range from the per-op interpreted
loop to segment-at-a-time XLA programs (and, later, pallas kernels or a
remote device runtime):

* ``execute(*flat_inputs) -> List[Any]`` — run on concrete flat inputs,
* ``as_fn() -> Callable`` — a JAX-traceable replay of the same program,
* ``stats: ExecutorStats`` — the transparency counters.

Backends register themselves by name; ``get_backend`` resolves the name
from ``PipelineConfig.backend`` / ``forge_compile(..., backend=...)``.
"""
from __future__ import annotations

import threading
import time
from abc import ABC, abstractmethod
from contextlib import contextmanager
from typing import (
    Any,
    Callable,
    Dict,
    Iterator,
    List,
    Optional,
    Protocol,
    Type,
    runtime_checkable,
)

from repro.runtime.trace import span

from ..lowering import RGIRProgram

#: per-thread running total of seconds spent in XLA's compile of backend
#: programs; a compile reads it before and after (compile-service workers
#: build on their own threads)
_xla_clock = threading.local()


def xla_compile_seconds() -> float:
    """Seconds this thread has spent in :func:`xla_compiling` so far."""
    return getattr(_xla_clock, "total", 0.0)


@contextmanager
def xla_compiling() -> Iterator[None]:
    """Time one XLA compile of a backend program, in an ``xla.compile``
    span."""
    t0 = time.perf_counter()
    try:
        with span("xla.compile"):
            yield
    finally:
        _xla_clock.total = xla_compile_seconds() + time.perf_counter() - t0


@runtime_checkable
class ExecutorLike(Protocol):
    """What the compiler needs back from a backend."""

    stats: Any

    def execute(self, *flat_inputs: Any) -> List[Any]:
        ...

    def as_fn(self) -> Callable:
        ...


class Backend(ABC):
    """One Phase-4 code generator.  Subclasses set ``name``."""

    #: registry key; also recorded in ``CompilationResult.backend``
    name: str = "?"

    @abstractmethod
    def build(
        self,
        prog: RGIRProgram,
        *,
        reorder: bool = True,
        validate: bool = True,
    ) -> ExecutorLike:
        """Compile an RGIR program into an executor."""

    # -- persistence hooks (DESIGN.md §Async compilation & persistent
    # cache).  Both are best-effort: ``None`` means "this backend (or
    # this particular program) does not persist", and the compile cache
    # falls back to a full build.  An entry must be pure picklable data
    # — RGIR itself is NOT picklable (op targets are closures), so
    # entries store analysis products + serialized segment executables
    # and are rehydrated against a freshly lowered program of the same
    # fingerprint.

    def export_entry(
        self, prog: RGIRProgram, executor: ExecutorLike
    ) -> Optional[Dict[str, Any]]:
        """Serialize ``executor`` into a picklable disk-cache entry."""
        return None

    def build_from_entry(
        self,
        prog: RGIRProgram,
        entry: Dict[str, Any],
        *,
        reorder: bool = True,
        validate: bool = True,
    ) -> Optional[ExecutorLike]:
        """Rebuild an executor from a disk entry + fresh RGIR, or None."""
        return None

    def __repr__(self) -> str:  # pragma: no cover
        return f"<backend {self.name!r}>"


_REGISTRY: Dict[str, Backend] = {}


def register_backend(backend_cls: Type[Backend]) -> Type[Backend]:
    """Class decorator: instantiate + register under ``backend_cls.name``."""
    inst = backend_cls()
    if inst.name in _REGISTRY:
        raise ValueError(f"backend {inst.name!r} already registered")
    _REGISTRY[inst.name] = inst
    return backend_cls


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> List[str]:
    return sorted(_REGISTRY)
