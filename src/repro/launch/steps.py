"""Step-function builders shared by train.py, serve.py and dryrun.py.

``make_train_step(cfg)``  -> (params, opt_state, batch) -> (params,
opt_state, metrics) — forward (family-dispatched), cross-entropy loss,
grad, optimizer update.  ``make_serve_step(cfg)`` -> one-token greedy
decode against the KV/state cache.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..models import get_model, losses
from ..optim import Adafactor, AdamW

#: params above this use Adafactor (factored states; see DESIGN §7)
ADAFACTOR_THRESHOLD = 100e9


def dealias_tree(tree):
    """Force every leaf onto its own buffer.

    XLA's constant folding aliases identical outputs (e.g. the all-ones
    norm scales across layers, or AdamW's zero-initialized mu and nu) to
    one buffer; donating such a pytree then fails with "donate the same
    buffer twice".  A ``copy()`` per leaf guarantees unique buffers.
    """
    return jax.tree_util.tree_map(
        lambda x: x.copy() if hasattr(x, "copy") else x, tree
    )


def gather_cache_rows(cache, axes_spec, rows):
    """Extract batch rows ``rows`` of a decode cache as a small tree.

    Each batch-polymorphic leaf (per ``axes_spec``, a vmap-style tree
    prefix) keeps only the selected rows along its batch axis —
    ``len(rows)`` wide — while batch-free leaves pass through
    unchanged.  Eager jnp ops, no compiled program: this is the
    host-side half of slot preemption on the contiguous path (park one
    slot's KV/state rows) and of rung-crossing row moves.
    """
    from ..core.shapekey import flatten_axes

    flat, tree = jax.tree_util.tree_flatten(cache)
    axes = flatten_axes(axes_spec, cache)
    idx = jnp.asarray(rows, jnp.int32)
    out = []
    for leaf, ax in zip(flat, axes):
        out.append(leaf if ax is None else jnp.take(leaf, idx, axis=ax))
    return jax.tree_util.tree_unflatten(tree, out)


def blend_cache_rows(cache, axes_spec, row_tree, rows):
    """Write ``row_tree`` (a :func:`gather_cache_rows` extract) back
    into batch rows ``rows`` of ``cache``.

    The masked-blend dual of the gather: every non-selected row of
    every leaf survives bitwise, so a parked slot's rows swap back in
    without perturbing its neighbours (the resume half of contiguous
    preemption).  Batch-free leaves keep ``cache``'s values.
    """
    from ..core.shapekey import flatten_axes

    flat, tree = jax.tree_util.tree_flatten(cache)
    flat_src, _ = jax.tree_util.tree_flatten(row_tree)
    axes = flatten_axes(axes_spec, cache)
    idx = jnp.asarray(rows, jnp.int32)
    out = []
    for leaf, src, ax in zip(flat, flat_src, axes):
        if ax is None:
            out.append(leaf)
            continue
        out.append(jnp.moveaxis(
            jnp.moveaxis(leaf, ax, 0).at[idx].set(jnp.moveaxis(src, ax, 0)),
            0, ax,
        ))
    return jax.tree_util.tree_unflatten(tree, out)


def default_optimizer(cfg: ModelConfig):
    if cfg.param_count() > ADAFACTOR_THRESHOLD:
        return Adafactor(lr=1e-3)
    return AdamW(lr=3e-4)


def make_forward(cfg: ModelConfig) -> Callable:
    model = get_model(cfg)
    if cfg.family == "encdec":
        def fwd(params, batch):
            return model.apply(params, batch["frames"], batch["tokens"], cfg)
    elif cfg.family == "vlm":
        def fwd(params, batch):
            return model.module.apply(
                params, batch["tokens"], cfg, patch_embeds=batch["patches"]
            )
    else:
        def fwd(params, batch):
            return model.apply(params, batch["tokens"], cfg)
    return fwd


def make_loss_fn(cfg: ModelConfig) -> Callable:
    fwd = make_forward(cfg)

    def loss_fn(params, batch):
        logits = fwd(params, batch)
        return losses.cross_entropy(logits, batch["labels"])

    return loss_fn


def make_train_step(cfg: ModelConfig, optimizer=None) -> Callable:
    optimizer = optimizer or default_optimizer(cfg)
    loss_fn = make_loss_fn(cfg)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        metrics = {"loss": loss.astype(jnp.float32)}
        return new_params, new_opt, metrics

    return train_step


def make_eval_step(cfg: ModelConfig) -> Callable:
    loss_fn = make_loss_fn(cfg)

    def eval_step(params, batch):
        loss = loss_fn(params, batch)
        return {"loss": loss, "ppl": jnp.exp(loss)}

    return eval_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    fwd = make_forward(cfg)

    def prefill_step(params, batch):
        return fwd(params, batch)

    return prefill_step


#: sentinel emitted instead of an argmax over non-finite logits; never a
#: real token (vocab ids are >= 0), so the scheduler can quarantine the
#: row with a typed error while its neighbours decode on untouched
POISON_TOKEN = -1


def guarded_argmax(last_logits) -> jax.Array:
    """Greedy token with a non-finite tripwire.

    A row whose logits contain NaN/+Inf (a poisoned KV row, an overflow
    in a half-precision matmul) emits :data:`POISON_TOKEN`; rows with
    all-finite logits are bitwise-identical to a plain argmax (``-Inf``
    entries — legitimate vocab masking — keep the row max finite and do
    NOT trip it).  Same dispatch count: the check compiles into the
    decode program.
    """
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    row_max = jnp.max(last_logits, axis=-1)
    return jnp.where(jnp.isfinite(row_max), tok, POISON_TOKEN).astype(
        jnp.int32
    )


def make_serve_step(cfg: ModelConfig) -> Callable:
    model = get_model(cfg)

    def serve_step(params, cache, token, pos):
        logits, new_cache = model.decode_step(params, cache, token, pos, cfg)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok[:, None], new_cache

    return serve_step


#: families whose decode_step accepts per-row positions + slot masks —
#: the slot-level continuous-batching contract (vlm's M-RoPE stream and
#: encdec's cross-attention cache still assume one shared position)
SLOT_FAMILIES = ("dense", "moe", "hybrid", "ssm")


def supports_slot_decode(cfg: ModelConfig) -> bool:
    return cfg.family in SLOT_FAMILIES


def make_slot_serve_step(cfg: ModelConfig) -> Callable:
    """Slot-level greedy decode step for continuous batching.

    ``(params, cache, token(B, 1), pos(B,), slot_mask(B,)) ->
    (next_tok(B, 1), new_cache)``: each batch row writes its KV/state
    and masks attention at its OWN position, and rows with
    ``slot_mask[b] == False`` leave their cache rows bitwise untouched
    (their emitted token is garbage and must be ignored).  The scalar
    variant (:func:`make_serve_step`) remains the group-lockstep
    baseline.
    """
    if not supports_slot_decode(cfg):
        raise ValueError(
            f"family {cfg.family!r} has no slot-level decode "
            f"(supported: {', '.join(SLOT_FAMILIES)})"
        )
    model = get_model(cfg)

    def slot_step(params, cache, token, pos, slot_mask):
        logits, new_cache = model.decode_step(
            params, cache, token, pos, cfg, slot_mask=slot_mask
        )
        next_tok = guarded_argmax(logits[:, -1, :])
        return next_tok[:, None], new_cache

    return slot_step


def supports_batched_prefill(cfg: ModelConfig) -> bool:
    """Can this family prefill a whole (B, S) prompt block in one
    dispatch?

    The single source of truth for every serve front: True when the
    family module exposes a ``prefill_step`` whose one-pass result
    reproduces sequential decode — attention KV caches (causal chunk
    write) and, via the chunked state scan, the recurrent families
    (rg-lru associative scan, mLSTM (C, n, m) scan, sLSTM in-program
    ``lax.scan``).  False only where the algorithm itself couples
    tokens across the block.
    """
    return get_model(cfg).prefill_step is not None


def make_slot_prefill_step(cfg: ModelConfig):
    """Slot-masked whole-prompt prefill for mid-generation swap-in.

    ``(params, cache, tokens(B, S), pos, slot_mask(B,)) -> (logits,
    cache)`` — plus a trailing ``length(B,)`` arg when the model
    declares ``prefill_takes_length`` (recurrent state consumes every
    chunk token, so the scan must know where each row's real prompt
    ends).  One forward pass writes the S-token block into the cache
    rows of the *masked* slots only — every other slot's cache survives
    bitwise, so a queued prompt can be prefilled into a finished slot
    while its neighbours are mid-generation.  None for families without
    a batched prefill — those swap in through masked decode-step replay
    instead.
    """
    model = get_model(cfg)
    if not supports_batched_prefill(cfg) or not supports_slot_decode(cfg):
        return None

    if model.prefill_takes_length:
        def slot_prefill(params, cache, tokens, pos, slot_mask, length):
            return model.prefill_step(
                params, cache, tokens, pos, cfg, slot_mask=slot_mask,
                length=length,
            )
    else:
        def slot_prefill(params, cache, tokens, pos, slot_mask):
            return model.prefill_step(
                params, cache, tokens, pos, cfg, slot_mask=slot_mask
            )

    return slot_prefill


def make_batched_prefill_step(cfg: ModelConfig):
    """Whole-prompt prefill step for the 2-D bucketed serve front.

    ``(params, cache, tokens(B, S), pos) -> ((B, S, vocab) logits,
    cache)``: one forward pass folds the whole prompt block into the
    cache — causal chunk write for KV families, chunked state scan for
    the recurrent families.  Returns None only where a whole-block pass
    cannot reproduce sequential decode — the server then prefills
    sequentially through ``decode_step``.
    """
    model = get_model(cfg)
    if not supports_batched_prefill(cfg):
        return None

    def prefill_step(params, cache, tokens, pos):
        return model.prefill_step(params, cache, tokens, pos, cfg)

    return prefill_step


def supports_paged_decode(cfg: ModelConfig) -> bool:
    """Paged KV is a transformer-cache concept: only families whose decode
    state is a pure positional KV cache can swap it for a page pool
    (recurrent/state caches fold past tokens into non-positional state)."""
    model = get_model(cfg)
    return (
        model.paged_decode_step is not None
        and supports_slot_decode(cfg)
        and not model.stateful_decode
    )


def paged_store(cache):
    """The store of a paged cache: every entry but its ``page_table`` (K and
    V pages, or an MLA config's latent pages, and any counter the model
    keeps beside them)."""
    return {k: v for k, v in cache.items() if k != "page_table"}


def make_paged_serve_step(cfg: ModelConfig) -> Callable:
    """Slot-level greedy decode against the paged KV pool.

    ``(params, store, page_table(B, MP), token(B, 1), pos(B,),
    slot_mask(B,)) -> (next_tok(B, 1), new_store)``: same contract as
    :func:`make_slot_serve_step`, but the per-slot KV rows live behind a
    page table into a shared page pool (``store``: :func:`paged_store`).
    The table is read-only here — allocation happens host-side in the
    scheduler — so swap-in/resize is a table edit, never a KV copy.
    """
    if not supports_paged_decode(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged decode path")
    model = get_model(cfg)

    def paged_step(params, store, page_table, token, pos, slot_mask):
        cache = dict(store, page_table=page_table)
        logits, new_cache = model.paged_decode_step(
            params, cache, token, pos, cfg, slot_mask=slot_mask
        )
        with jax.named_scope("logits"):
            next_tok = guarded_argmax(logits[:, -1, :])
        return next_tok[:, None], paged_store(new_cache)

    return paged_step


def make_paged_prefill_step(cfg: ModelConfig):
    """Slot-masked whole-prompt prefill into the paged KV pool.

    ``(params, store, page_table(B, MP), tokens(B, S), pos(B,),
    slot_mask(B,), last(B,)) -> ((B, vocab) logits, new_store)``.  ``pos``
    is per-row: a row whose leading pages were matched in the prefix tree
    anchors its chunk at the skip offset, so prefix-hit and cold rows
    prefill in the same dispatch.  The logits are those of each row's
    column ``last`` (its last real prompt token) alone: the padded block's
    other columns never reach the head.  None for families without a
    batched prefill.
    """
    if not supports_paged_decode(cfg):
        return None
    model = get_model(cfg)
    if model.paged_prefill_step is None:
        return None

    def paged_prefill(params, store, page_table, tokens, pos, slot_mask, last):
        cache = dict(store, page_table=page_table)
        logits, new_cache = model.paged_prefill_step(
            params, cache, tokens, pos, cfg, slot_mask=slot_mask, last=last
        )
        return logits[:, 0], paged_store(new_cache)

    return paged_prefill


# NOTE: the exact-shape forge serve-step builder that used to live here
# (make_forge_serve_step) was removed with the rebuild-per-shape server:
# launch/serve.py now compiles the decode step behind a ShapeKey
# bucketing front (ForgeCompiler.compile_bucketed), so batch-size
# transitions dispatch instead of rebuilding.
