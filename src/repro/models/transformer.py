"""Model composition: layer descriptors -> scanned stacks -> full LMs.

Every architecture is a sequence of *stacks*; a stack is a layer group
(e.g. Gemma-3's [local x5, global]) repeated R times and executed with
``lax.scan`` over stacked parameters, so HLO size is O(group), not
O(depth) — the property that makes 100-layer x 512-device AOT compiles
tractable and keeps compile times production-sane.

Layer kinds: global / local (self-attn), cross (gated cross-attn,
vision), selfcross (self+cross, enc-dec decoder), rwkv, rglru.
MLP kinds: dense / moe / chanmix.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
from jax.ad_checkpoint import checkpoint_name as _ckpt_name
import jax.numpy as jnp

from repro.core import integration as ci
from repro.distributed.sharding import constrain
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import rwkv6 as RW
from repro.models.param import Param, init_tree, axes_tree, stack_specs


@dataclasses.dataclass(frozen=True)
class LayerDesc:
    kind: str          # global | local | cross | selfcross | rwkv | rglru
    mlp: str           # dense | moe | chanmix


@dataclasses.dataclass(frozen=True)
class StackPlan:
    descs: tuple[LayerDesc, ...]
    repeats: int
    start: int         # absolute index of first layer (debug/logging)


def layer_descs(cfg) -> tuple[LayerDesc, ...]:
    kinds = cfg.layer_kinds
    out = []
    for i, kind in enumerate(kinds):
        if kind == "rwkv":
            mlp = "chanmix"
        elif cfg.moe is not None and i >= cfg.moe.first_dense_layers:
            mlp = "moe"
        else:
            mlp = "dense"
        out.append(LayerDesc(kind, mlp))
    return tuple(out)


def plan_stacks(cfg) -> tuple[StackPlan, ...]:
    """Segment depth into maximal scanned groups (+ tails)."""
    descs = layer_descs(cfg)
    n = len(descs)
    if not cfg.scan_layers:   # fully unrolled (FLOP-accounting compiles)
        return tuple(StackPlan((d,), 1, i) for i, d in enumerate(descs))
    p = len(cfg.pattern)
    # segment boundaries where the mlp-kind regime changes (deepseek's
    # first-dense-layers prefix)
    bounds = [0]
    for i in range(1, n):
        if descs[i].mlp != descs[i - 1].mlp:
            bounds.append(i)
    bounds.append(n)
    plans = []
    for s0, s1 in zip(bounds[:-1], bounds[1:]):
        seg = descs[s0:s1]
        g = len(seg) // p
        if g > 0:
            plans.append(StackPlan(tuple(seg[:p]), g, s0))
        tail = seg[g * p:]
        if tail:
            plans.append(StackPlan(tuple(tail), 1, s0 + g * p))
    return tuple(plans)


# ------------------------------------------------------------- blocks


def block_specs(cfg, desc: LayerDesc):
    d = cfg.d_model
    nt = cfg.norm_type
    s = {"pre_norm": L.norm_specs(d, nt)}
    if desc.kind in ("global", "local"):
        s["attn"] = MLA.mla_specs(cfg) if cfg.mla else A.attn_specs(cfg)
    elif desc.kind == "cross":
        s["attn"] = A.attn_specs(cfg, kv_input_dim=d)
        s["gate_attn"] = Param((1,), (None,), "zeros")
        s["gate_mlp"] = Param((1,), (None,), "zeros")
    elif desc.kind == "selfcross":
        s["attn"] = A.attn_specs(cfg)
        s["cross_norm"] = L.norm_specs(d, nt)
        s["cross"] = A.attn_specs(cfg, kv_input_dim=d)
    elif desc.kind == "rwkv":
        s["attn"] = RW.timemix_specs(cfg)
    elif desc.kind == "rglru":
        s["attn"] = RG.rglru_specs(cfg)
    else:
        raise ValueError(desc.kind)
    if cfg.norm_style == "sandwich":
        s["post_attn_norm"] = L.norm_specs(d, nt)
        s["pre_mlp_norm"] = L.norm_specs(d, nt)
        s["post_mlp_norm"] = L.norm_specs(d, nt)
    else:
        s["mlp_norm"] = L.norm_specs(d, nt)
    if desc.mlp == "dense":
        s["mlp"] = L.mlp_specs(d, cfg.d_ff)
    elif desc.mlp == "moe":
        s["mlp"] = MOE.moe_specs(cfg)
    elif desc.mlp == "chanmix":
        s["mlp"] = RW.chanmix_specs(cfg)
    return s


def init_block_cache(cfg, desc: LayerDesc, batch: int, capacity: int,
                     memory_len: int = 0, dtype=jnp.bfloat16):
    """Decode-state for one layer (None for train)."""
    if desc.kind in ("global", "local"):
        if cfg.mla:
            return MLA.make_cache(cfg, batch, capacity, dtype=dtype)
        if desc.kind == "local":
            capacity = min(capacity, cfg.window)  # ring buffer == window
        return A.make_cache(cfg, batch, capacity, dtype=dtype)
    if desc.kind == "cross":
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((batch, memory_len, kv, hd), dtype),
                "v": jnp.zeros((batch, memory_len, kv, hd), dtype)}
    if desc.kind == "selfcross":
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        return {"self": A.make_cache(cfg, batch, capacity, dtype=dtype),
                "cross": {"k": jnp.zeros((batch, memory_len, kv, hd), dtype),
                          "v": jnp.zeros((batch, memory_len, kv, hd),
                                         dtype)}}
    if desc.kind == "rwkv":
        return RW.make_state(cfg, batch, dtype=dtype)
    if desc.kind == "rglru":
        return RG.make_state(cfg, batch, dtype=dtype)
    raise ValueError(desc.kind)


def _norm(p, x, cfg):
    return L.apply_norm(p, x, kind=cfg.norm_type,
                        method=cfg.reduce_method,
                        fast_apply=getattr(cfg, "fast_norm", False))


def block_apply(params, cfg, desc: LayerDesc, x, cache, *, positions,
                memory=None, decode=False, causal=True):
    """Returns (x, new_cache, aux_loss)."""
    sandwich = cfg.norm_style == "sandwich"
    aux = jnp.zeros((), jnp.float32)
    h = _norm(params["pre_norm"], x, cfg)
    new_cache = cache

    if desc.kind in ("global", "local"):
        if cfg.mla:
            out, new_cache = MLA.mla_attention(
                params["attn"], cfg, h, positions=positions, cache=cache,
                decode=decode)
        else:
            out, new_cache = A.attention(
                params["attn"], cfg, h, positions=positions,
                kind=desc.kind, cache=cache, decode=decode, causal=causal)
    elif desc.kind == "cross":
        out, new_cache = A.attention(
            params["attn"], cfg, h, positions=positions, kind="cross",
            cache=cache, memory=memory, decode=decode)
        out = out * jnp.tanh(params["gate_attn"].astype(out.dtype))
    elif desc.kind == "selfcross":
        out, self_c = A.attention(
            params["attn"], cfg, h, positions=positions, kind="global",
            cache=None if cache is None else cache["self"], decode=decode)
        x = x + (_norm(params["post_attn_norm"], out, cfg)
                 if sandwich else out)
        h = _norm(params["cross_norm"], x, cfg)
        out, cross_c = A.attention(
            params["cross"], cfg, h, positions=positions, kind="cross",
            cache=None if cache is None else cache["cross"],
            memory=memory, decode=decode)
        if cache is not None:
            new_cache = {"self": self_c, "cross": cross_c}
    elif desc.kind == "rwkv":
        state = cache if cache is not None else RW.make_state(
            cfg, x.shape[0])
        out, new_state = RW.time_mix(params["attn"], cfg, h, state)
        new_cache = new_state if cache is not None else None
    elif desc.kind == "rglru":
        state = cache if cache is not None else RG.make_state(
            cfg, x.shape[0])
        out, new_state = RG.rglru_apply(params["attn"], cfg, h, state)
        new_cache = new_state if cache is not None else None
    else:
        raise ValueError(desc.kind)

    if desc.kind != "selfcross":
        if sandwich:
            out = _norm(params["post_attn_norm"], out, cfg)
        # §Perf: name the mixer output so remat="dots_tagged" can save it
        # (skips re-running chunked attention / recurrences in backward).
        out = _ckpt_name(out, "mixer_out")
        x = x + out

    norm_key = "pre_mlp_norm" if sandwich else "mlp_norm"
    nm_method = getattr(cfg, "norm_matmul_method", "")
    if (desc.mlp == "dense" and nm_method
            and cfg.norm_type == "rmsnorm"):
        # Fused norm->matmul boundary: one `norm_matmul` dispatch
        # replaces rmsnorm + the up/gate projections — the normalized
        # activations never reach HBM under the fused engine.
        out = L.fused_mlp(
            params[norm_key], params["mlp"], x, act=cfg.act,
            method=nm_method,
            precision=getattr(cfg, "norm_matmul_precision", None),
            objective=getattr(cfg, "norm_matmul_slo_ms", None),
            bf16_out=getattr(cfg, "bf16_activation_ar", False))
    elif desc.mlp == "dense":
        h = _norm(params[norm_key], x, cfg)
        out = L.mlp(params["mlp"], h, act=cfg.act,
                    bf16_out=getattr(cfg, "bf16_activation_ar", False))
    else:
        h = _norm(params[norm_key], x, cfg)
    if desc.mlp == "moe":
        out, aux = MOE.moe_block(params["mlp"], cfg, h)
    elif desc.mlp == "chanmix":
        state = new_cache if new_cache is not None else RW.make_state(
            cfg, x.shape[0])
        out, state = RW.channel_mix(params["mlp"], cfg, h, state)
        if new_cache is not None:
            new_cache = state
    if desc.kind == "cross":
        out = out * jnp.tanh(params["gate_mlp"].astype(out.dtype))
    if sandwich:
        out = _norm(params["post_mlp_norm"], out, cfg)
    out = _ckpt_name(out, "mlp_out")
    x = x + out
    return x, new_cache, aux


# ------------------------------------------------------------- stacks


def stack_param_specs(cfg, plan: StackPlan):
    group = {f"L{i}": block_specs(cfg, d) for i, d in enumerate(plan.descs)}
    return stack_specs(group, plan.repeats)


def init_stack_cache(cfg, plan: StackPlan, batch, capacity, memory_len,
                     dtype=jnp.bfloat16):
    group = {f"L{i}": init_block_cache(cfg, d, batch, capacity, memory_len,
                                       dtype)
             for i, d in enumerate(plan.descs)}
    return jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf[None], (plan.repeats,)
                                      + leaf.shape).copy(), group)


def run_stack(params, cfg, plan: StackPlan, x, cache, aux, *, positions,
              memory=None, decode=False, causal=True):
    """Scan the stack's groups. Returns (x, new_cache, aux)."""

    def group_fn(carry, scans):
        xc, auxc = carry
        gp, gc = scans
        new_gc = {} if gc is not None else None
        for i, desc in enumerate(plan.descs):
            sub = None if gc is None else gc[f"L{i}"]
            xc, nc, a = block_apply(gp[f"L{i}"], cfg, desc, xc, sub,
                                    positions=positions, memory=memory,
                                    decode=decode, causal=causal)
            if new_gc is not None:
                new_gc[f"L{i}"] = nc
            auxc = auxc + a
        return (xc, auxc), new_gc

    if cfg.remat == "full":
        group_fn = jax.checkpoint(group_fn,
                                  prevent_cse=False)
    elif cfg.remat == "dots":
        group_fn = jax.checkpoint(
            group_fn, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif cfg.remat == "dots_tagged":
        # dots policy + named saves: mixer outputs and the MoE post-a2a /
        # expert-output buffers survive to backward, so neither the
        # attention inner scans nor the MoE dispatch (incl. its
        # all-to-alls) are re-executed during transposition (§Perf).
        group_fn = jax.checkpoint(
            group_fn, prevent_cse=False,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "mixer_out", "mlp_out", "moe_post_a2a",
                    "moe_expert_out")))

    if plan.repeats == 1:
        (x, aux), new_cache = group_fn(
            (x, aux),
            (jax.tree_util.tree_map(lambda l: l[0], params),
             None if cache is None else
             jax.tree_util.tree_map(lambda l: l[0], cache)))
        if new_cache is not None:
            new_cache = jax.tree_util.tree_map(lambda l: l[None], new_cache)
        return x, new_cache, aux

    (x, aux), new_cache = jax.lax.scan(group_fn, (x, aux), (params, cache))
    return x, new_cache, aux


# ------------------------------------------------------------- full LM


def backbone_specs(cfg):
    return {
        "stacks": {f"S{i}": stack_param_specs(cfg, p)
                   for i, p in enumerate(plan_stacks(cfg))},
        "final_norm": L.norm_specs(cfg.d_model, cfg.norm_type),
    }


def decoder_specs(cfg):
    specs = {"embed": L.embed_specs(cfg.vocab_size, cfg.d_model),
             **backbone_specs(cfg)}
    if cfg.rwkv is not None:
        # RWKV's ln0: a LayerNorm on the embeddings before block 0.
        specs["embed_norm"] = L.norm_specs(cfg.d_model, cfg.norm_type)
    if not cfg.tie_embeddings:
        specs["lm_head"] = Param((cfg.d_model, cfg.vocab_size),
                                 ("embed", "vocab"))
    if cfg.mtp:
        specs["mtp"] = {
            "proj": Param((2 * cfg.d_model, cfg.d_model),
                          ("embed", None)),
            "block": block_specs(cfg, LayerDesc("global", "dense")),
            "norm": L.norm_specs(cfg.d_model, cfg.norm_type),
        }
    return specs


def decoder_forward(params, cfg, tokens, *, positions=None, caches=None,
                    memory=None, decode=False, causal=True,
                    inputs_embeds=None):
    """tokens (B,S) -> (hidden (B,S,D), new_caches, aux)."""
    if inputs_embeds is not None:
        x = inputs_embeds.astype(cfg.compute_dtype)
    else:
        x = L.embed_lookup(
            params["embed"], tokens, scale=cfg.embed_scale,
            d=cfg.d_model, compute_dtype=cfg.compute_dtype,
            cast_table=getattr(cfg, "bf16_activation_ar", False),
            onehot=getattr(cfg, "onehot_embed", False))
    if "embed_norm" in params:
        x = _norm(params["embed_norm"], x, cfg)
    s = x.shape[1]
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    aux = jnp.zeros((), jnp.float32)
    plans = plan_stacks(cfg)
    new_caches = {} if caches is not None else None
    for i, plan in enumerate(plans):
        key = f"S{i}"
        c = None if caches is None else caches[key]
        x, nc, aux = run_stack(params["stacks"][key], cfg, plan, x, c, aux,
                               positions=positions, memory=memory,
                               decode=decode, causal=causal)
        if new_caches is not None:
            new_caches[key] = nc
    x = _norm(params["final_norm"], x, cfg)
    return x, new_caches, aux


def logits_from_hidden(params, cfg, x):
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], x, softcap=cfg.final_softcap)
    logits = x @ params["lm_head"].astype(x.dtype)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_softcap)
    return logits


def init_decoder_cache(cfg, batch: int, capacity: int, memory_len: int = 0,
                       dtype=jnp.bfloat16, start_index: int = 0):
    caches = {}
    for i, plan in enumerate(plan_stacks(cfg)):
        c = init_stack_cache(cfg, plan, batch, capacity, memory_len, dtype)
        caches[f"S{i}"] = c
    # set all idx fields to start_index
    def fix_idx(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "idx":
            return jnp.full(leaf.shape, start_index, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(fix_idx, caches)


_CACHE_LEAF_AXES = {
    "k": ("batch", None, "kv_heads", "head_dim"),
    "v": ("batch", None, "kv_heads", "head_dim"),
    "ckv": ("batch", None, "kv_lora"),
    "krope": ("batch", None, None),
    "idx": (),
    "wkv": ("batch", "heads", None, None),
    "x_tm": ("batch", None),
    "x_cm": ("batch", None),
    "h": ("batch", "lru"),
    "conv": ("batch", None, "lru"),
}


def cache_logical_axes(caches):
    """Logical-axes pytree matching a cache pytree (keyed on leaf name;
    a leading 'layers' axis is added for stacked leaves)."""
    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        base = _CACHE_LEAF_AXES[name]
        extra = leaf.ndim - len(base)
        return ("layers",) * extra + base
    return jax.tree_util.tree_map_with_path(one, caches)


# ------------------------------------------------------------- losses


def cross_entropy(logits, labels, mask, *, reduce_method="mma"):
    """Token CE with f32 logsumexp; reduction via the MMA engine."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    return ci.masked_mean(nll, mask, method=reduce_method)

def chunked_cross_entropy(params, cfg, hidden, labels, mask,
                          *, chunk: int):
    """CE without materialising (B, S, V) logits (§Perf): scan vocab
    chunks with an online logsumexp (the flash-attention trick applied
    to the loss), rematerialising each chunk's logits in backward.

    Peak loss-path memory drops from O(B*S*V) to O(B*S*chunk)."""
    if cfg.tie_embeddings:
        w = params["embed"]["table"]          # (V, D)
    else:
        w = params["lm_head"].T               # (V, D)
    v, d = w.shape
    pad = (-v) % chunk
    nck = (v + pad) // chunk
    x = hidden.astype(cfg.compute_dtype)
    cap = cfg.final_softcap
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))

    def body(carry, i):
        m_run, l_run, ll = carry
        start = i * chunk
        wc = jax.lax.dynamic_slice_in_dim(w, start, chunk, axis=0)
        logits = (x @ wc.T.astype(x.dtype)).astype(jnp.float32)
        if cap is not None:
            logits = cap * jnp.tanh(logits / cap)
        vocab_ids = start + jnp.arange(chunk)
        valid = vocab_ids < v
        logits = jnp.where(valid[None, None, :], logits, -2.0e38)
        m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
        l_new = l_run * jnp.exp(m_run - m_new) \
            + jnp.sum(jnp.exp(logits - m_new[..., None]), axis=-1)
        hit = vocab_ids[None, None, :] == labels[..., None]
        ll = ll + jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        return (m_new, l_new, ll), None

    b, s = labels.shape
    init = (jnp.full((b, s), -2.0e38, jnp.float32),
            jnp.zeros((b, s), jnp.float32),
            jnp.zeros((b, s), jnp.float32))
    (m_run, l_run, ll), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        init, jnp.arange(nck))
    lse = m_run + jnp.log(jnp.maximum(l_run, 1e-37))
    return ci.masked_mean(lse - ll, mask, method=cfg.reduce_method)
