"""Program spans and device scopes, on the profiler's own clock.

``span(name, **ids)`` is ``jax.profiler.TraceAnnotation``: it writes into
the profiler's host plane, so program spans share the device trace's
clock, and it costs about a microsecond when no profiler is active.
The model step names its ops with ``jax.named_scope`` (the scope lands
in each HLO op's ``op_name``), which is how device time is put down to a
part of the step.

The tuples below are every span and scope name the program emits; trace
readers import them rather than spelling the names again.
"""
from __future__ import annotations

import gc
from contextlib import contextmanager
from typing import Iterator

import jax

SPANS = (
    # the serving loop, one thread, nested: one scheduler tick holds
    # admission (with its prefill call), the rung change, the decode call,
    # the page-pool check, the token copy and retirement, and the sleep
    # toward the next arrival
    "serve.tick",
    "serve.admit",
    "serve.prefill",
    "serve.resize",
    "serve.dispatch",
    "serve.pool_check",
    "serve.harvest",
    "serve.wait_arrival",
    # one plan entry of a segment executor, inside a program call
    "forge.segment",
    # a full (generation 2) garbage collection during SlotScheduler.run
    "py.gc",
    # set-up: the four Forge phases of one compile, and XLA's compile of
    # one segment program (inside forge.backend)
    "forge.capture",
    "forge.optimize",
    "forge.lower",
    "forge.backend",
    "xla.compile",
)

#: device scopes of the model step; ``kv.*`` and ``mla.attend`` (latent
#: attention: absorbed scores, softmax, latent sum, W_UV) nest inside
#: ``attn``, ``moe.*`` (routing, the held experts' grouped matmuls, the
#: shared experts) inside ``mlp``
SCOPES = ("kv.write", "kv.gather", "mla.attend", "attn",
          "moe.route", "moe.experts", "moe.shared", "mlp", "logits")

span = jax.profiler.TraceAnnotation


@contextmanager
def gc_spans() -> Iterator[None]:
    """Emit a ``py.gc`` span around every full (generation 2) collection
    while the block runs."""
    open_spans = []

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            s = span("py.gc")
            s.__enter__()
            open_spans.append(s)
        elif open_spans:
            open_spans.pop().__exit__(None, None, None)

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
        while open_spans:
            open_spans.pop().__exit__(None, None, None)
