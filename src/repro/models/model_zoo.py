"""Public model API: build(cfg) -> Model with init / loss / prefill /
decode_step / input_specs for every assigned architecture family.

Batch layouts (all inputs ShapeDtypeStruct-compatible for the dry-run):
  train:   {tokens (B,S) i32, labels (B,S) i32, mask (B,S) f32}
           [+ vision_embeds (B,V,D) | src_embeds (B,S,D) for vlm/audio]
  prefill: {tokens (B,S)} [+ modality inputs]      -> (last logits, caches)
  decode:  {token (B,1), pos (), caches}           -> (logits, caches)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import integration as ci
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.param import (axes_tree, count_params, init_tree,
                                shapes_tree)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any
    specs: Any
    init: Callable
    loss: Callable          # (params, batch) -> (loss, metrics)
    logits: Callable        # (params, batch) -> (B, S, V) full-seq logits
    prefill: Callable       # (params, batch) -> (logits, caches)
    decode_step: Callable   # (params, batch) -> (logits, caches)
    input_specs: Callable   # (shape_cfg) -> batch pytree of SDS
    cache_specs: Callable   # (shape_cfg) -> caches pytree of SDS

    def param_axes(self):
        return axes_tree(self.specs)

    def param_shapes(self):
        return shapes_tree(self.specs)

    def num_params(self) -> int:
        return count_params(jax.tree_util.tree_leaves(self.param_shapes()))


def _encoder_cfg(cfg):
    return dataclasses.replace(
        cfg, num_layers=cfg.encoder_layers, pattern=("global",),
        moe=None, mla=None, mtp=False, attn_softcap=None)


def _full_specs(cfg):
    specs = T.decoder_specs(cfg)
    if cfg.is_encdec:
        specs["encoder"] = T.backbone_specs(_encoder_cfg(cfg))
    return specs


def _memory(params, cfg, batch):
    """Cross-attention memory: encoder output (audio) or vision embeds."""
    if cfg.is_encdec:
        enc_cfg = _encoder_cfg(cfg)
        x, _, _ = T.decoder_forward(
            params["encoder"], enc_cfg, None, causal=False,
            inputs_embeds=batch["src_embeds"])
        return x
    if cfg.vision_tokens:
        return batch["vision_embeds"].astype(cfg.compute_dtype)
    return None


def _mtp_loss(params, cfg, hidden, tokens, labels, mask):
    """DeepSeek MTP: one extra block predicts token t+2 from
    (h_t, embed(token_{t+1}))."""
    mp = params["mtp"]
    emb_next = L.embed_lookup(params["embed"], tokens, scale=False,
                              d=cfg.d_model,
                              compute_dtype=cfg.compute_dtype)
    # shift: h_t pairs with embedding of t+1 (== tokens shifted left)
    h = hidden[:, :-1]
    e = emb_next[:, 1:]
    z = jnp.concatenate([h, e], axis=-1) @ mp["proj"].astype(h.dtype)
    s = z.shape[1]
    desc = T.LayerDesc("global", "dense")
    z, _, _ = T.block_apply(mp["block"], cfg, desc, z, None,
                            positions=jnp.arange(s, dtype=jnp.int32))
    z = L.apply_norm(mp["norm"], z, kind=cfg.norm_type,
                     method=cfg.reduce_method)
    logits = T.logits_from_hidden(params, cfg, z)
    # labels for t+2 = labels shifted left by one
    lbl = labels[:, 1:]
    msk = mask[:, 1:]
    return T.cross_entropy(logits, lbl, msk,
                           reduce_method=cfg.reduce_method)


def build(cfg) -> Model:
    specs = _full_specs(cfg)

    def init(key):
        # Weights are drawn in f32 and stored in cfg.param_dtype.
        return jax.tree_util.tree_map(
            lambda p: p.astype(cfg.param_dtype), init_tree(key, specs))

    def loss(params, batch):
        memory = _memory(params, cfg, batch)
        hidden, _, aux = T.decoder_forward(
            params, cfg, batch["tokens"], memory=memory)
        chunk = getattr(cfg, "ce_vocab_chunk", 0)
        if chunk:
            ce = T.chunked_cross_entropy(
                params, cfg, hidden, batch["labels"], batch["mask"],
                chunk=chunk)
        else:
            logits = T.logits_from_hidden(params, cfg, hidden)
            ce = T.cross_entropy(logits, batch["labels"], batch["mask"],
                                 reduce_method=cfg.reduce_method)
        total = ce
        metrics = {"ce": ce}
        if cfg.moe is not None:
            total = total + cfg.moe.aux_loss_weight * aux
            metrics["aux"] = aux
        if cfg.mtp:
            mtp = _mtp_loss(params, cfg, hidden, batch["tokens"],
                            batch["labels"], batch["mask"])
            total = total + cfg.mtp_loss_weight * mtp
            metrics["mtp"] = mtp
        metrics["loss"] = total
        return total, metrics

    def logits_fn(params, batch):
        """Full-sequence teacher-forcing logits (B, S, V) — the scoring
        path (``repro.launch.serve.Server.score``).  Unlike ``prefill``
        (which keeps only the last position for the decode loop), every
        position's logits survive; no caches are allocated."""
        memory = _memory(params, cfg, batch)
        hidden, _, _ = T.decoder_forward(
            params, cfg, batch["tokens"], memory=memory)
        return T.logits_from_hidden(params, cfg, hidden)

    def _decode_capacity(shape_cfg):
        return shape_cfg.seq_len

    def prefill(params, batch, *, extra_capacity: int = 64):
        """Run the prompt; allocate caches with decode headroom."""
        memory = _memory(params, cfg, batch)
        tokens = batch["tokens"]
        b, s = tokens.shape
        mem_len = 0 if memory is None else memory.shape[1]
        caches = T.init_decoder_cache(cfg, b, s + extra_capacity, mem_len)
        hidden, caches, _ = T.decoder_forward(
            params, cfg, tokens, caches=caches, memory=memory)
        logits = T.logits_from_hidden(params, cfg, hidden[:, -1:])
        return logits, caches

    def decode_step(params, batch):
        """One token for the whole batch against existing caches.

        ``pos`` is a scalar () when every row sits at the same
        position (the fixed-batch ``Server.generate`` loop), or (B,)
        per-slot absolute positions (the continuous-batching engine:
        each slot serves its own request at its own depth).
        """
        caches = batch["caches"]
        pos = jnp.asarray(batch["pos"], jnp.int32)
        positions = pos[:, None] if pos.ndim == 1 else pos[None]
        hidden, caches, _ = T.decoder_forward(
            params, cfg, batch["token"], positions=positions,
            caches=caches, decode=True)
        logits = T.logits_from_hidden(params, cfg, hidden)
        return logits, caches

    def input_specs(shape_cfg):
        b, s = shape_cfg.global_batch, shape_cfg.seq_len
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
        bf16 = functools.partial(jax.ShapeDtypeStruct,
                                 dtype=jnp.bfloat16)
        extra = {}
        if cfg.vision_tokens:
            extra["vision_embeds"] = bf16((b, cfg.vision_tokens,
                                           cfg.d_model))
        if cfg.is_encdec:
            src = s if shape_cfg.kind != "decode" else shape_cfg.seq_len
            extra["src_embeds"] = bf16((b, src, cfg.d_model))
        if shape_cfg.kind == "train":
            return {"tokens": i32((b, s)), "labels": i32((b, s)),
                    "mask": f32((b, s)), **extra}
        if shape_cfg.kind == "prefill":
            return {"tokens": i32((b, s)), **extra}
        # decode: token + pos + caches
        return {"token": i32((b, 1)),
                "pos": jax.ShapeDtypeStruct((), jnp.int32),
                "caches": cache_specs(shape_cfg)}

    def cache_specs(shape_cfg):
        b, s = shape_cfg.global_batch, shape_cfg.seq_len
        mem_len = cfg.vision_tokens or (s if cfg.is_encdec else 0)
        caches = jax.eval_shape(
            lambda: T.init_decoder_cache(cfg, b, s, mem_len))
        return caches

    return Model(cfg=cfg, specs=specs, init=init, loss=loss,
                 logits=logits_fn, prefill=prefill,
                 decode_step=decode_step, input_specs=input_specs,
                 cache_specs=cache_specs)
