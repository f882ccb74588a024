"""Top-level models: CausalLM (dense/moe/hybrid/ssm/vlm) and EncDecLM (audio).

Pure-functional: `init_lm_params` builds the param pytree (usable under
jax.eval_shape for allocation-free dry-runs), `lm_loss` / `decode_step` are
the train/serve entry points the launchers jit.

C3-SL integration (single-program mode): when a codec is supplied, the layer
stack is split at the superblock midpoint; the cut activation (B, S, d) is
flattened to (B, S*d) per-sample features and round-tripped through the
codec — batch-wise grouping over B, exactly the paper's Algorithm 1 with
D = S*d_model.  (The pod-pipeline mode in repro.core.split does the same
across the pod mesh axis with the payload on the wire.)

Modality frontends (vlm/audio) are stubs per the brief: batches carry
precomputed patch/frame embeddings; a linear projector maps them to d_model.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import stack as stack_lib
from repro.models.layers import embed_init, dense_init, softmax_cross_entropy
from repro.models.stack import _apply_norm, _init_norm

ENC_PATTERN = (("attn", "mlp"),)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_lm_params(rng, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, 8)
    p: dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "stack": stack_lib.init_stack(ks[1], cfg, dtype),
        "final_norm": _init_norm(cfg, dtype),
        "head": dense_init(ks[2], cfg.d_model, cfg.vocab_size, dtype),
    }
    if cfg.first_dense_layers:
        p["first"] = stack_lib.init_superblock(ks[3], cfg, dtype, dense_mlp=True)
    if cfg.frontend:
        p["frontend_proj"] = dense_init(ks[4], cfg.frontend_dim, cfg.d_model, dtype)
    if cfg.is_encdec:
        import dataclasses
        enc_cfg = dataclasses.replace(cfg, block_pattern=ENC_PATTERN,
                                      num_layers=cfg.encoder_layers,
                                      first_dense_layers=0)
        p["encoder"] = {"stack": stack_lib.init_stack(ks[5], enc_cfg, dtype),
                        "norm": _init_norm(cfg, dtype)}
    return p


def abstract_params(cfg: ModelConfig, dtype=jnp.float32):
    """ShapeDtypeStruct params — no allocation (dry-run path)."""
    return jax.eval_shape(lambda r: init_lm_params(r, cfg, dtype),
                          jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _encoder_cfg(cfg: ModelConfig):
    import dataclasses
    return dataclasses.replace(cfg, block_pattern=ENC_PATTERN,
                               num_layers=cfg.encoder_layers, first_dense_layers=0)


def _embed_inputs(params, cfg: ModelConfig, batch):
    """Token (+frontend) embedding.  Returns (h (B,S,d), positions (B,S))."""
    tokens = batch["tokens"]
    h = params["embed"][tokens]
    if cfg.frontend and not cfg.is_encdec:
        # VLM: [patch embeddings ; text tokens], total length = frontend_seq + S_text
        fe = batch["frontend"] @ params["frontend_proj"]
        h = jnp.concatenate([fe.astype(h.dtype), h], axis=1)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return h, positions


def _run_encoder(params, cfg: ModelConfig, frontend_emb, remat=True):
    enc_cfg = _encoder_cfg(cfg)
    h = frontend_emb @ params["frontend_proj"]
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h, _ = stack_lib.apply_stack(params["encoder"]["stack"], enc_cfg, h, positions,
                                 remat=remat)
    return _apply_norm(cfg, params["encoder"]["norm"], h)


def _split_stacked(stacked, n_front: int):
    front = jax.tree.map(lambda a: a[:n_front], stacked)
    back = jax.tree.map(lambda a: a[n_front:], stacked)
    return front, back


def lm_forward(params, batch, cfg: ModelConfig, *, codec=None, codec_params=None,
               sliding_window=None, remat=True, last_only=False,
               with_metrics=False, bwd_probe=None, erasure=None):
    """Returns (logits (B,S,V), aux_loss) — or (logits, aux_loss, metrics)
    with ``with_metrics=True``, where metrics carries ``cut_snr`` (the
    retrieval SNR in dB at the cut layer, the Adaptive-R controller's signal;
    absent without a codec).  last_only=True slices the final position BEFORE
    the head matmul (serving prefill: never materializes the (B, S, V)
    logits).

    ``codec`` may be a static codec or a static ``repro.transport.SplitLink``
    (per-direction cut-layer codecs); for an asymmetric link, ``bwd_probe``
    is the gradient-SNR tap — differentiate the loss w.r.t. it and the
    resulting "gradient" is the measured server→client gradient-retrieval
    SNR in dB (see ``repro.transport.channel.grad_roundtrip``).

    ``erasure`` (``{"fwd": keep[, "bwd": keep]}``) injects cut-payload loss
    into the round-trip: masks are runtime arguments with bucket-static
    shapes (see ``repro.transport.link.roundtrip``), ``None`` is
    structurally the pre-fault trace."""
    sliding_window = sliding_window if sliding_window is not None else cfg.sliding_window
    memory = None
    if cfg.is_encdec:
        memory = _run_encoder(params, cfg, batch["frontend"], remat=remat)
    h, positions = _embed_inputs(params, cfg, batch)
    aux = jnp.array(0.0, jnp.float32)
    if cfg.first_dense_layers:
        h, a = stack_lib.apply_superblock(params["first"], cfg, h, positions,
                                          memory=memory, sliding_window=sliding_window)
        aux = aux + a

    run = functools.partial(stack_lib.apply_stack, cfg=cfg, positions=positions,
                            memory=memory, sliding_window=sliding_window, remat=remat)
    metrics = {}
    if codec is None:
        h, a = run(params["stack"], h=h)
        aux = aux + a
    else:
        n_cut = cfg.num_superblocks // 2
        front, back = _split_stacked(params["stack"], n_cut)
        h, a1 = run(front, h=h)
        B, S, d = h.shape
        Zf = h.reshape(B, S * d)
        from repro.transport.link import roundtrip
        if with_metrics:
            Zhat, snr = roundtrip(codec, codec_params, Zf, with_snr=True,
                                  bwd_probe=bwd_probe, erasure=erasure)
            metrics["cut_snr"] = snr
        else:
            Zhat = roundtrip(codec, codec_params, Zf, bwd_probe=bwd_probe,
                             erasure=erasure)
        h = Zhat.reshape(B, S, d)
        h, a2 = run(back, h=h)
        aux = aux + a1 + a2

    if last_only:
        h = h[:, -1:, :]
    h = _apply_norm(cfg, params["final_norm"], h)
    logits = h @ params["head"]
    if with_metrics:
        return logits, aux, metrics
    return logits, aux


def lm_loss(params, batch, cfg: ModelConfig, *, codec=None, codec_params=None,
            sliding_window=None, remat=True, with_metrics=False,
            bwd_probe=None, erasure=None):
    """Mean next-token CE (+ MoE aux).  labels == -1 are masked (vlm pads
    frontend positions).  ``with_metrics=True`` returns (loss, metrics) with
    the cut-layer ``cut_snr`` (see lm_forward) — the signal the Adaptive-R
    codec scheduler consumes in repro.launch.train.  ``codec`` may be a
    static ``SplitLink``; ``bwd_probe`` taps the gradient-retrieval SNR and
    ``erasure`` injects cut-payload loss (see lm_forward)."""
    out = lm_forward(params, batch, cfg, codec=codec,
                     codec_params=codec_params,
                     sliding_window=sliding_window, remat=remat,
                     with_metrics=with_metrics, bwd_probe=bwd_probe,
                     erasure=erasure)
    logits, aux = out[0], out[1]
    labels = batch["labels"]
    if cfg.frontend and not cfg.is_encdec:
        pad = jnp.full((labels.shape[0], cfg.frontend_seq), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    mask = labels >= 0
    ce = softmax_cross_entropy(logits, jnp.maximum(labels, 0), mask)
    loss = ce + cfg.aux_loss_weight * aux
    if with_metrics:
        return loss, out[2]
    return loss


# ---------------------------------------------------------------------------
# serving (one-token decode with cache)
# ---------------------------------------------------------------------------

def init_decode_cache(params, cfg: ModelConfig, batch: int, length: int,
                      dtype=jnp.float32, frontend_emb=None, paged=None):
    """Decode cache pytree.  With ``paged`` (a repro.models.paging.PagedLayout)
    the per-position leaves become shared page pools and the cache carries
    the per-slot page tables under "pages" (full-length caches) and
    "pages_swa" (sliding-window rings) — int32 (B, P) arrays of physical
    page ids the serving engine rewrites at admit/retire boundaries."""
    cache: dict[str, Any] = {
        "stack": stack_lib.init_stack_cache(cfg, batch, length, dtype,
                                            paged=paged)}
    if cfg.first_dense_layers:
        cache["first"] = stack_lib.init_superblock_cache(cfg, batch, length,
                                                         dtype, paged=paged)
    if cfg.is_encdec:
        assert frontend_emb is not None
        cache["memory"] = _run_encoder(params, cfg, frontend_emb, remat=False)
    if paged is not None:
        cache["pages"] = jnp.zeros((batch, paged.pages_per_slot), jnp.int32)
        if paged.len_swa:
            cache["pages_swa"] = jnp.zeros((batch, paged.pages_per_slot_swa),
                                           jnp.int32)
    return cache


def abstract_decode_cache(cfg: ModelConfig, batch: int, length: int,
                          dtype=jnp.float32):
    """Cache ShapeDtypeStructs without touching params (dry-run path)."""
    cache: dict[str, Any] = {
        "stack": jax.eval_shape(
            lambda: stack_lib.init_stack_cache(cfg, batch, length, dtype))}
    if cfg.first_dense_layers:
        cache["first"] = jax.eval_shape(
            lambda: stack_lib.init_superblock_cache(cfg, batch, length, dtype))
    if cfg.is_encdec:
        cache["memory"] = jax.ShapeDtypeStruct(
            (batch, cfg.frontend_seq, cfg.d_model), dtype)
    return cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig, *,
                codec=None, codec_params=None, paged=None, live=None,
                return_cut=False, kv_read="gather"):
    """tokens (B, 1) int32; pos scalar int32.  Returns (logits (B,1,V), cache').

    With a codec, the cut-layer feature (B, d_model) is compressed batch-wise
    across the decode batch — the serving-path C3-SL integration.  ``paged``
    (static PagedLayout, matching the cache built with it) switches the
    per-position cache leaves to pool+page-table addressing; ``live`` (B,)
    masks every cache/state write for rows that are not decoding AND zeroes
    their cut-layer contribution to the batch-wise codec, so a dead slot's
    stale cache state can never perturb live rows through cross-talk.

    ``return_cut=True`` (static) additionally returns the (B, d_model)
    cut-layer feature exactly as it enters ``codec.encode`` — the
    post-live-mask tensor — so the sanitizer tier can check the
    superposition-hygiene invariant (dead rows contribute exactly zero)
    against the REAL code path rather than a reimplementation.  None on
    the codec-free path, which has no cut.

    ``kv_read="kernel"`` (static) routes the stacked superblocks' GQA cache
    reads through the Pallas paged-attention kernel (bit-identical to the
    gather read — see repro.kernels.paged_attention).  The unstacked
    first-dense superblock stays on the gather read: its cache is a
    separate, non-scanned pytree the kernel tier does not cover yet.
    """
    h = params["embed"][tokens]
    memory = cache.get("memory")
    pages, pages_swa = cache.get("pages"), cache.get("pages_swa")
    kw = dict(memory=memory, paged=paged, pages=pages, pages_swa=pages_swa,
              live=live)
    new_cache = dict(cache)
    cut = None
    if cfg.first_dense_layers:
        h, new_cache["first"] = stack_lib.apply_superblock_decode(
            params["first"], cache["first"], cfg, h, pos, **kw)

    if codec is None:
        h, new_cache["stack"] = stack_lib.apply_stack_decode(
            params["stack"], cache["stack"], cfg, h, pos, kv_read=kv_read,
            **kw)
    else:
        # both sides of the cut run on the same stacked weights and cache,
        # each updating its own layers in place: neither is split at the cut
        n_cut, n_sb = cfg.num_superblocks // 2, cfg.num_superblocks
        h, c_stack = stack_lib.apply_stack_decode(
            params["stack"], cache["stack"], cfg, h, pos, kv_read=kv_read,
            layers=range(n_cut), **kw)
        B, _, d = h.shape
        if live is not None:
            # A non-live row's cut-layer feature is attention over whatever
            # its (possibly stale) page-table rows point at — i.e. garbage
            # that depends on allocation history.  It must not leak into the
            # batch-wise superposition: zero it so dead slots contribute
            # nothing and live outputs are a function of live state only.
            h = jnp.where(live[:, None, None], h, 0.0)
        cut = h.reshape(B, d)
        payload = codec.encode(codec_params, cut)
        h = codec.decode(codec_params, payload).reshape(B, 1, d)
        h, new_cache["stack"] = stack_lib.apply_stack_decode(
            params["stack"], c_stack, cfg, h, pos, kv_read=kv_read,
            layers=range(n_cut, n_sb), **kw)

    h = _apply_norm(cfg, params["final_norm"], h)
    if return_cut:
        return h @ params["head"], new_cache, cut
    return h @ params["head"], new_cache


# ---------------------------------------------------------------------------
# serving (chunked prefill: C prompt tokens per dispatch)
# ---------------------------------------------------------------------------

def chunk_forward(params, cache, tokens, pos, cfg: ModelConfig, *,
                  codec=None, codec_params=None, valid=None, paged=None):
    """Shared C-positions-per-dispatch forward: the write path under both
    chunked prefill and the speculative verify/commit round.

    tokens (B,C) int32; pos (B,) int32 per-row start positions; valid (B,C)
    bool marks real tokens — False entries (ragged chunk tails, rows that
    are not ingesting, rejected draft positions) write nothing to the KV
    cache and advance no recurrent state.  Returns
    ``(h, new_cache, cut_seq)`` with ``h`` (B,C,d) the PRE-NORM final
    hidden states and ``cut_seq`` the (B,C,d) cut-layer features exactly
    as they entered the codec (post valid-mask; None without a codec).

    With a codec, the cut-layer features are compressed batch-wise PER
    POSITION: transposing into the ``sequence_group_encode`` layout
    (C,B,d) makes each group of R consecutive rows R slots at the same
    position — the same group shape the decode path forms from its (B,d)
    features (B divisible by R).  Non-valid positions contribute exact
    ZEROS to the superposition — mirroring decode's ``live`` masking — so
    padding and rejected speculation never inject cache-history-dependent
    cross-talk.
    """
    B, C = tokens.shape
    if valid is None:
        valid = jnp.ones((B, C), bool)
    h = params["embed"][tokens]
    memory = cache.get("memory")
    pages, pages_swa = cache.get("pages"), cache.get("pages_swa")
    kw = dict(memory=memory, paged=paged, pages=pages, pages_swa=pages_swa)
    new_cache = dict(cache)
    cut_seq = None
    if cfg.first_dense_layers:
        h, new_cache["first"] = stack_lib.apply_superblock_prefill(
            params["first"], cache["first"], cfg, h, pos, valid, **kw)

    if codec is None:
        h, new_cache["stack"] = stack_lib.apply_stack_prefill(
            params["stack"], cache["stack"], cfg, h, pos, valid, **kw)
    else:
        from repro.codecs.c3sl import (sequence_group_decode,
                                       sequence_group_encode)
        n_cut, n_sb = cfg.num_superblocks // 2, cfg.num_superblocks
        h, c_stack = stack_lib.apply_stack_prefill(
            params["stack"], cache["stack"], cfg, h, pos, valid,
            layers=range(n_cut), **kw)
        # same containment as decode_step: positions that are not real
        # prompt tokens (idle slots, ragged chunk tails) carry garbage
        # features that would otherwise superpose onto live rows — and vary
        # with cache/page history.  Zero them before the encode.
        h = jnp.where(valid[:, :, None], h, 0.0)
        cut_seq = h
        payload = sequence_group_encode(codec, codec_params, h.swapaxes(0, 1))
        h = sequence_group_decode(codec, codec_params, payload,
                                  C, B).swapaxes(0, 1)
        h, new_cache["stack"] = stack_lib.apply_stack_prefill(
            params["stack"], c_stack, cfg, h, pos, valid,
            layers=range(n_cut, n_sb), **kw)
    return h, new_cache, cut_seq


def prefill_chunk(params, cache, tokens, pos, cfg: ModelConfig, *,
                  codec=None, codec_params=None, valid=None, paged=None):
    """Ingest C prompt tokens per row in ONE dispatch (vs C decode dispatches).

    tokens (B,C) int32; pos (B,) int32 per-row start positions; valid (B,C)
    bool marks real tokens — False entries (ragged chunk tails, or rows that
    are not prefilling at all) write nothing to the KV cache and advance no
    recurrent state.  Returns (logits (B,V) at each row's LAST VALID
    position, new_cache); rows with no valid token get garbage logits the
    caller must ignore.

    Chunked prefill reproduces prefill-as-decode outputs token-for-token
    when the codec group CONTENTS also match, i.e. every slot ingests in
    lockstep (full batch, equal prompt lengths); with ragged prompts the
    two paths group different LIVE contents per step, so outputs agree
    only up to codec cross-talk — same as any occupancy change does under
    batch-wise compression.  See :func:`chunk_forward` for the masking
    and per-position grouping semantics.
    """
    B, C = tokens.shape
    if valid is None:
        valid = jnp.ones((B, C), bool)
    h, new_cache, _ = chunk_forward(params, cache, tokens, pos, cfg,
                                    codec=codec, codec_params=codec_params,
                                    valid=valid, paged=paged)
    last = jnp.maximum(valid.sum(-1).astype(jnp.int32) - 1, 0)
    h_last = h[jnp.arange(B), last]                              # (B,d)
    h_last = _apply_norm(cfg, params["final_norm"], h_last)
    return h_last @ params["head"], new_cache


def verify_chunk(params, cache, tokens, pos, cfg: ModelConfig, *,
                 codec=None, codec_params=None, valid=None, paged=None):
    """Speculative VERIFY phase: k-position forward, per-position logits,
    cache writes DISCARDED.

    tokens (B,k) carries each row's last verified token followed by its
    k-1 draft proposals; ``valid`` should mark live rows (all k positions
    — acceptance is decided from the returned logits, after the fact).
    Returns ``(logits (B,k,V), feat (B,k,d))`` where ``feat`` is the
    cut-layer feature sequence (post valid-mask, exactly what the codec
    encoded) or, without a codec, the pre-norm final hidden states — the
    position-(e-1) row of it is the draft head's feedback feature for the
    next round.

    The updated cache is intentionally NOT returned: no speculative write
    may survive — the commit phase re-ingests only the accepted prefix
    through :func:`chunk_forward` with a ``j < e`` valid mask, so
    rejection rollback is pure position truncation and partially-written
    pages can never leak into later superpositions.  Per-position logits
    at position j are exact (equal to vanilla decode's) whenever every
    earlier position's input matched vanilla's — the acceptance rule only
    consumes logits inside that prefix.

    Cost: the forward writes the verify chunk into the stacked cache in
    place (see ``stack._scan_layers``) and this function drops the result,
    so XLA must keep the old pools alive beside the written ones — a copy
    of every pool per verify round that the in-place update avoids
    everywhere else.
    """
    h, _, cut_seq = chunk_forward(params, cache, tokens, pos, cfg,
                                  codec=codec, codec_params=codec_params,
                                  valid=valid, paged=paged)
    feat = cut_seq if codec is not None else h
    hn = _apply_norm(cfg, params["final_norm"], h)
    return hn @ params["head"], feat


# ---------------------------------------------------------------------------
# pod-pipeline adapter (repro.core.split.make_pod_pipeline_loss_fn callables)
# ---------------------------------------------------------------------------

def make_pipeline_fns(cfg: ModelConfig):
    """(embed_fn, stage_fn, head_loss_fn) for the 2-stage pod pipeline.

    `params["blocks"]` must be the stacked superblocks reshaped to a leading
    stage axis of 2: tree.map(lambda a: a.reshape(2, N//2, *a.shape[1:])).
    """

    def embed_fn(embed_p, x_mb):
        h = embed_p["embed"][x_mb]
        return h

    def stage_fn(blocks_local, h):
        B, S = h.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h, _ = stack_lib.apply_stack(blocks_local, cfg, h, positions, remat=True)
        return h

    def head_loss_fn(head_p, h, y_mb):
        h = _apply_norm(cfg, head_p["final_norm"], h)
        logits = h @ head_p["head"]
        return softmax_cross_entropy(logits, jnp.maximum(y_mb, 0), y_mb >= 0)

    return embed_fn, stage_fn, head_loss_fn


def split_stack_for_pipeline(stacked, n_stages: int = 2):
    return jax.tree.map(
        lambda a: a.reshape(n_stages, a.shape[0] // n_stages, *a.shape[1:]), stacked)
