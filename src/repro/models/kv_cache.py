"""Paged KV cache: fixed-size pages + per-slot page tables, with
quantize-on-write and compensated dequant.

The continuous-batching engine (``repro.launch.serve``) stores every
slot's decoder KV state here instead of in one monolithic dense tree:

  * each **paged leaf** — a cache-dict float leaf with an ``idx``
    sibling, i.e. the positional KV buffers ``k``/``v`` (GQA) and
    ``ckv``/``krope`` (MLA) — owns a page *pool* of fixed-size pages
    plus a per-slot **page table** mapping the slot's token positions
    onto pool pages.  Per-leaf capacities differ (a local ring buffer
    allocates ``cap == window``), so tables and pages-per-slot are
    per-leaf while the allocator's free list is shared per leaf pool;
  * **quantize-on-write**: with ``quant='int8'`` a token's feature
    vector is stored as int8 codes with one f32 scale per (page slot,
    token) — the hi word — plus, when the precision policy keeps
    ``split_words >= 2``, a bf16 **residual** word, mirroring the
    split-word decomposition of the ``mma_ec`` engine family
    (``repro.core.precision.split_f32_words``).  Dequant recombines
    the words through the compensated ``repro.core.precision.two_sum``
    so the reconstruction is the exactly-rounded two-word sum, and the
    paged cache tracks the dense one within an ``MmaPolicy`` error
    budget.  ``quant='none'`` stores raw leaf values (bit-exact — the
    mode the engine's bit-identity contract runs under);
  * non-positional leaves (cross-attention ``k``/``v`` memory, RWKV /
    RG-LRU recurrent state) and the ``idx`` counters stay **dense**,
    written per-slot on admission.

Layout of one paged leaf (dense shape ``(layers, B, cap, *feat)``):

  codes  (num_pages, page_size, F)   int8 | leaf dtype   F = prod(feat')
  scale  (num_pages, page_size)      f32                 int8 only
  resid  (num_pages, page_size, F)   bf16                split_words>=2
  table  (num_slots, ceil(cap / page_size))  int32, -1 = unmapped

where ``feat'`` is the slot view ``(cap, layers, *feat)`` with the
token axis moved first — token position ``t`` of slot ``s`` lives at
``(table[s, t // page_size], t % page_size)``.

Each eager copy of a dense per-slot leaf (``write_slot``,
``write_token``) writes a new copy of the whole leaf; it adds the
leaf's bytes to counter ``store.state_bytes`` (``repro.obs``), keyed by
the leaf's path, from the shape alone: no device work, no sync.

The allocator enforces the scheduler's slot-lifecycle invariants
(``alloc_slot`` on a live slot and ``free_slot`` / ``write`` on a free
one raise), which is what the admit/evict tests probe.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.precision import as_policy, two_sum
from repro.models.transformer import _CACHE_LEAF_AXES

# Cache-dict float leaves that carry one entry per token position —
# pageable iff an ``idx`` sibling marks the dict as a positional cache
# (cross-attention memory has k/v but no idx, and stays dense).
PAGED_LEAF_NAMES = frozenset({"k", "v", "ckv", "krope"})

_INT8_MAX = 127.0


def _walk(tree, path=()):
    """Yield (path, parent_dict, leaf) over a nested-dict cache tree."""
    if isinstance(tree, dict):
        for key in sorted(tree):
            yield from _walk(tree[key], path + (key,))
    else:
        yield path, None, tree


def _leaf_paths(tree):
    """(path -> leaf) plus the set of paths eligible for paging."""
    leaves, paged = {}, set()
    def rec(node, path):
        if isinstance(node, dict):
            has_idx = "idx" in node
            for key in sorted(node):
                sub = path + (key,)
                child = node[key]
                if isinstance(child, dict):
                    rec(child, sub)
                else:
                    leaves[sub] = child
                    if has_idx and key in PAGED_LEAF_NAMES and \
                            jnp.issubdtype(jnp.dtype(child.dtype),
                                           jnp.floating):
                        paged.add(sub)
        else:
            leaves[path] = node
    rec(tree, ())
    return leaves, paged


def _tree_set(tree, path, value):
    """Return a copy of a nested-dict tree with ``tree[*path] = value``."""
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = _tree_set(tree[path[0]], path[1:], value)
    return out


def _tree_get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


@dataclasses.dataclass
class _PagedLeaf:
    """Pools + table for one paged leaf."""
    codes: jax.Array                 # (P, page, F)
    scale: Optional[jax.Array]       # (P, page) f32 — int8 only
    resid: Optional[jax.Array]       # (P, page, F) bf16 — 2-word quant
    table: jax.Array                 # (num_slots, pages_per_slot) i32
    free: list                       # free page ids (allocator state)
    shape: tuple                     # dense leaf shape
    dtype: object                    # dense leaf dtype
    batch_axis: int
    token_axis: int
    capacity: int
    pages_per_slot: int
    feat_shape: tuple                # slot-view feature dims


def _axes_of(name: str, ndim: int) -> tuple:
    """(batch_axis, token_axis) of a paged leaf from its name, allowing
    leading stacked-layer axes (``init_stack_cache`` broadcasting)."""
    # Every paged leaf's base layout is (batch, token, *feat); stacked
    # leaves carry `extra` leading layer axes.
    base_ndim = {"k": 4, "v": 4, "ckv": 3, "krope": 3}[name]
    extra = ndim - base_ndim
    if extra < 0:
        raise ValueError(f"cache leaf {name!r} has rank {ndim}, "
                         f"expected >= {base_ndim}")
    return extra, extra + 1


class PagedKVCache:
    """Slot-addressed paged storage for one decoder cache geometry.

    ``template`` is a dense cache pytree (as ``init_decoder_cache``
    builds — concrete arrays or ShapeDtypeStructs) whose batch dim is
    ``num_slots``; its paged leaves become page pools, everything else
    becomes dense per-slot storage.  ``quant='int8'`` quantizes on
    write (codes + scale, plus a bf16 residual word when the policy
    keeps ``split_words >= 2``); ``quant='none'`` stores raw values.
    """

    def __init__(self, template, *, num_slots: int, page_size: int = 16,
                 quant: str = "int8", precision=None):
        if quant not in ("int8", "none"):
            raise ValueError(f"quant must be 'int8' or 'none', "
                             f"got {quant!r}")
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.quant = quant
        self.policy = as_policy(precision)
        words = 2 if self.policy is None else int(self.policy.split_words)
        self.residual = quant == "int8" and words >= 2
        self._live: set = set()
        leaves, paged_paths = _leaf_paths(template)
        self._paged: dict = {}
        self._dense: dict = {}
        self._dense_batch_axis: dict = {}
        for path, leaf in leaves.items():
            shape = tuple(leaf.shape)
            dtype = jnp.dtype(leaf.dtype)
            if path in paged_paths:
                self._paged[path] = self._make_pool(path[-1], shape,
                                                    dtype)
            else:
                self._dense[path] = jnp.zeros(shape, dtype)
                base = _CACHE_LEAF_AXES.get(path[-1], ())
                if "batch" in base:
                    extra = len(shape) - len(base)
                    self._dense_batch_axis[path] = \
                        extra + base.index("batch")
                else:
                    self._dense_batch_axis[path] = None
        self._template = template  # structure/shape reference only

    # ------------------------------------------------------- pools

    def _make_pool(self, name: str, shape: tuple, dtype) -> _PagedLeaf:
        batch_axis, token_axis = _axes_of(name, len(shape))
        if shape[batch_axis] != self.num_slots:
            raise ValueError(
                f"cache leaf {name!r} batch dim {shape[batch_axis]} "
                f"!= num_slots {self.num_slots}")
        cap = shape[token_axis]
        pps = math.ceil(cap / self.page_size)
        feat = tuple(d for i, d in enumerate(shape)
                     if i not in (batch_axis, token_axis))
        f = math.prod(feat) if feat else 1
        num_pages = self.num_slots * pps
        code_dtype = jnp.int8 if self.quant == "int8" else dtype
        return _PagedLeaf(
            codes=jnp.zeros((num_pages, self.page_size, f), code_dtype),
            scale=(jnp.zeros((num_pages, self.page_size), jnp.float32)
                   if self.quant == "int8" else None),
            resid=(jnp.zeros((num_pages, self.page_size, f),
                             jnp.bfloat16) if self.residual else None),
            table=jnp.full((self.num_slots, pps), -1, jnp.int32),
            free=list(range(num_pages - 1, -1, -1)),
            shape=shape, dtype=dtype, batch_axis=batch_axis,
            token_axis=token_axis, capacity=cap, pages_per_slot=pps,
            feat_shape=feat)

    # --------------------------------------------------- allocator

    @property
    def live_slots(self) -> frozenset:
        return frozenset(self._live)

    def slot_pages(self, slot: int) -> dict:
        """{leaf path: page-id list} — page-table inspection."""
        return {path: [int(p) for p in pl.table[slot]]
                for path, pl in self._paged.items()}

    def free_pages(self) -> dict:
        return {path: len(pl.free) for path, pl in self._paged.items()}

    def alloc_slot(self, slot: int) -> None:
        """Map every leaf's pages for ``slot`` (must be free)."""
        if slot in self._live:
            raise RuntimeError(
                f"slot {slot} is live; evict (free_slot) before "
                f"re-admitting — slots are never reused in place")
        if not 0 <= slot < self.num_slots:
            raise IndexError(f"slot {slot} out of range "
                             f"[0, {self.num_slots})")
        for pl in self._paged.values():
            if len(pl.free) < pl.pages_per_slot:
                raise RuntimeError("page pool exhausted")
            ids = [pl.free.pop() for _ in range(pl.pages_per_slot)]
            pl.table = pl.table.at[slot].set(jnp.asarray(ids, jnp.int32))
        self._live.add(slot)

    def free_slot(self, slot: int) -> None:
        """Evict ``slot``: return its pages to the free lists."""
        if slot not in self._live:
            raise RuntimeError(f"slot {slot} is not live")
        for pl in self._paged.values():
            pl.free.extend(int(p) for p in pl.table[slot])
            pl.table = pl.table.at[slot].set(-1)
        self._live.discard(slot)

    # ------------------------------------------------------ writes

    def _quantize(self, x):
        """(T, F) f32 -> (codes, scale, resid) per the write policy."""
        if self.quant == "none":
            return x, None, None
        xf = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1)
        scale = jnp.maximum(amax / _INT8_MAX, 1e-20)
        codes = jnp.clip(jnp.round(xf / scale[..., None]),
                         -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
        hi = codes.astype(jnp.float32) * scale[..., None]
        resid = (xf - hi).astype(jnp.bfloat16) if self.residual else None
        return codes, scale, resid

    def _slot_view(self, pl: _PagedLeaf, leaf, slot_in_leaf: int):
        """One slot's (cap, F) token-major view of a dense leaf."""
        sv = jnp.take(leaf, slot_in_leaf, axis=pl.batch_axis)
        sv = jnp.moveaxis(sv, pl.batch_axis, 0)  # token axis now first
        return sv.reshape(pl.capacity, -1)

    def write_slot(self, slot: int, caches) -> None:
        """Admit one request's cache into ``slot``.

        ``caches`` is a dense cache tree of batch 1 (an admission
        prefill run with ``extra_capacity`` topping the prompt up to
        this store's capacities) — every paged leaf is quantized page
        by page; dense leaves copy their batch row.
        """
        if slot not in self._live:
            raise RuntimeError(f"slot {slot} not allocated")
        leaves, _ = _leaf_paths(caches)
        for path, pl in self._paged.items():
            leaf = leaves[path]
            if leaf.shape[pl.token_axis] != pl.capacity:
                raise ValueError(
                    f"leaf {'/'.join(path)}: capacity "
                    f"{leaf.shape[pl.token_axis]} != {pl.capacity} "
                    f"(prefill with matching extra_capacity)")
            sv = self._slot_view(pl, leaf, 0)
            pad = pl.pages_per_slot * self.page_size - pl.capacity
            if pad:
                sv = jnp.pad(sv, ((0, pad), (0, 0)))
            codes, scale, resid = self._quantize(sv)
            pages = pl.table[slot]
            shape = (pl.pages_per_slot, self.page_size, -1)
            pl.codes = pl.codes.at[pages].set(
                codes.reshape(shape).astype(pl.codes.dtype))
            if scale is not None:
                pl.scale = pl.scale.at[pages].set(
                    scale.reshape(shape[:2]))
            if resid is not None:
                pl.resid = pl.resid.at[pages].set(resid.reshape(shape))
        for path, arr in self._dense.items():
            src = leaves[path]
            axis = self._dense_batch_axis[path]
            if axis is None:
                # step counters (and any batchless state) are shared
                self._dense[path] = jnp.broadcast_to(
                    jnp.asarray(src), arr.shape).astype(arr.dtype)
                continue
            # dense per-slot leaf (cross-attn memory, recurrent
            # state): copy the admission batch row into the slot row
            row = jnp.take(src, 0, axis=axis)
            self._copy_row(path, arr, axis, slot, row)

    def write_token(self, caches, slot: int, position: int) -> None:
        """Write one freshly-decoded token's KV for ``slot``.

        ``caches`` is the full dense tree a decode step returned
        (batch = num_slots); only the page entry holding ``position``
        (ring-wrapped per leaf: ``position % cap``) is touched, so
        earlier tokens are never re-quantized and quantization error
        does not compound over steps.
        """
        if slot not in self._live:
            raise RuntimeError(f"slot {slot} not allocated")
        leaves, _ = _leaf_paths(caches)
        for path, pl in self._paged.items():
            sv = self._slot_view(pl, leaves[path], slot)
            w = int(position) % pl.capacity
            x = sv[w][None]                          # (1, F)
            codes, scale, resid = self._quantize(x)
            page = pl.table[slot, w // self.page_size]
            off = w % self.page_size
            pl.codes = pl.codes.at[page, off].set(
                codes[0].astype(pl.codes.dtype))
            if scale is not None:
                pl.scale = pl.scale.at[page, off].set(scale[0])
            if resid is not None:
                pl.resid = pl.resid.at[page, off].set(resid[0])
        # recurrent / dense per-slot state advances every step too:
        # copy this slot's batch row from the step's full tree
        for path, arr in self._dense.items():
            axis = self._dense_batch_axis[path]
            if axis is None:
                continue
            row = jnp.take(leaves[path], slot, axis=axis)
            self._copy_row(path, arr, axis, slot, row)

    def _copy_row(self, path, arr, axis: int, slot: int, row) -> None:
        """Set ``slot``'s row of dense leaf ``path``: an eager update
        that writes a new copy of the whole leaf."""
        self._dense[path] = arr.at[
            (slice(None),) * axis + (slot,)].set(row.astype(arr.dtype))
        obs.count("store.state_bytes", "/".join(path), arr.nbytes)

    # ------------------------------------------------------- reads

    def _dequant_pages(self, pl: _PagedLeaf, gathered, scale, resid):
        x = gathered.astype(jnp.float32)
        if scale is not None:
            x = x * scale[..., None]
        if resid is not None:
            # compensated two-word recombination (the mma_ec form):
            # hi + lo through TwoSum keeps the exactly-rounded sum
            hi, lo = two_sum(x, resid.astype(jnp.float32))
            x = hi + lo
        return x

    def as_dense(self):
        """Materialise the dense cache tree (gather + dequant) the
        decode step consumes.  Unmapped (free) slots read as zeros."""
        out = self._template
        for path, pl in self._paged.items():
            valid = pl.table >= 0                    # (S, pps)
            safe = jnp.maximum(pl.table, 0)
            gathered = jnp.take(pl.codes, safe, axis=0)  # (S,pps,pg,F)
            scale = None if pl.scale is None else \
                jnp.take(pl.scale, safe, axis=0)
            resid = None if pl.resid is None else \
                jnp.take(pl.resid, safe, axis=0)
            if self.quant == "none":
                x = gathered.astype(jnp.float32)
            else:
                x = self._dequant_pages(pl, gathered, scale, resid)
            x = jnp.where(valid[..., None, None], x, 0.0)
            x = x.reshape(self.num_slots, -1,
                          x.shape[-1])[:, :pl.capacity]
            x = x.reshape((self.num_slots, pl.capacity) + pl.feat_shape)
            x = jnp.moveaxis(x, (0, 1), (pl.batch_axis, pl.token_axis))
            out = _tree_set(out, path, x.astype(pl.dtype))
        for path, arr in self._dense.items():
            out = _tree_set(out, path, arr)
        return out

    # --------------------------------------------------- utilities

    def read_slot(self, slot: int) -> dict:
        """{leaf path: (cap, F) f32} dequantized token-major content of
        one live slot (tests / debugging)."""
        if slot not in self._live:
            raise RuntimeError(f"slot {slot} not allocated")
        out = {}
        dense = self.as_dense()
        for path, pl in self._paged.items():
            out[path] = self._slot_view(pl, _tree_get(dense, path),
                                        slot).astype(jnp.float32)
        return out
