"""The paged KV cache: its layout, its writes and reads, and which kernel
reads it.  The one owner: nothing else in the package knows what a pool
looks like.

A *pool* is one layer's pages, shared by every request the serving
engine holds (``serving/kv_blocks.py`` hands the pages out): K and V as
``[num_blocks, block_size, groups, head_dim]``, or int8 with
per-(block, position, group) fp32 absmax scales; for a model with
learned sparse attention (``cfg.dsa_index_heads``) a third array, the
indexer's one key head, ``[num_blocks, block_size, index_width]``,
written beside K and V at the same place (``index_width`` is the
indexer's head size filled up with zeros to the TPU's 128 lanes: a
narrower last dimension is laid out, and fetched, at that width
anyway, and a slice of it cannot be addressed).  A model with latent
attention (``cfg.kv_lora_rank``) has ONE array a layer in place of K and
V, ``latent_pages`` ``[num_blocks, block_size, latent_width]``: a
token's row is its normed latent, then the one rotary key every head
shares, then zeros up to whole lanes (512 + 64 -> 640 at the published
widths: 1,280 B a token a layer in bf16 where 32 heads of keys of 192
and values of 128 would hold 20,480), and the row is the key AND, in its
first ``kv_lora_rank`` columns, the value of all the absorbed query
heads of a decode step, while a chunk's kernel expands each block of
rows into per-head keys and values in VMEM (``attend_latent``,
``expands_latents``).  A latent model WITH an indexer (the selection
over latents) has TWO arrays a layer, ``latent_pages`` and
``index_pages`` ``[num_blocks, block_size, index_width]``, the indexer's
key written beside the row at the same place (640 + 128 values at the
published widths: 1,536 B a token a layer where 64 heads of keys and
values of 256 would hold 65,536), and each query attends the rows its
indexer chooses, in the same two forms under a mask
(``_attend_latent_selected``).  Whatever a pool holds, a page
of it is a page of every array: the page programs below are
``tree_map``s, so copy-on-write, the prefix cache's adoption and the
host tier carry a page's indexer keys with its keys and values.  Block 0 is the
reserved garbage block: padded chunk tokens and idle slots write there
and nobody reads it unmasked.  All slots share the pool, so HBM is
sized for aggregate traffic, not ``num_slots x max_len`` (the ragged
paged-attention memory model, arXiv:2604.15464).  The engine keeps the
pools (a list, one a layer) as an opaque pytree and ONE :class:`CachePlan`
of its model's cache (``plan``): the pools to make, the tables a program
takes, what a launch counts.  ``block_bytes`` and the three page
programs' bodies (``copy_page``, ``fetch_page``, ``load_page``) are all
it needs beside that.

A model with a layer type per layer (``cfg.layer_types``) has TWO GROUPS
of pools: its ``full`` layers keep a request's pages for its whole
length, its ``sliding`` layers (group ``window``) only the pages a
future query's window can still reach, so the window group has its own,
smaller, number of blocks, its own free list and its own block table a
slot (``serving/kv_blocks.py``).  A page index means a page of every
layer OF ONE GROUP; ``layer_groups`` says which layer is of which.  A
model of one layer type has one group and its pools are as they always
were, whatever its window.

A model with state-space layers (``cfg.state_space``) has a THIRD kind
of per-request state, group ``state``: a ``mamba`` layer keeps no keys
and no pages but two arrays of fixed size a request, indexed by SLOT:
``conv_state`` ``[slots + 1, d_conv - 1, conv_dim]`` (the last columns
before the convolution; the taps lie in the sublanes: a last dimension
of 3 would be laid out at 128 lanes) and ``ssm_state`` ``[slots + 1,
heads, d_head, d_state]`` in float32 (``SSM_STATE_DTYPE``: a recurrence
multiplied and added to over a request's every token).  The last row is
the garbage row an idle row writes to, as page 0 is.  A launch whose
``context_lens`` is 0 reads zeros whatever the slot held
(``read_state``), so a slot is reused with no clearing launch.  A
decode step's recurrence is the cache's (``step_state``), on its two
paths as attention is: the in-place kernel over the live rows
(``ops/pallas/ssm_step.py``) or every row read, advanced and put back.
Its attention layers are of the ``full`` group.  The page programs,
copy-on-write and ``block_bytes`` are for pools WITH pages:
``paged_pools`` picks them, and ``array_shapes`` gives the state's
arrays a list of their own.  Which arrays a slot holds is the layer's
KIND's (``cfg.state_layer``): a gated short convolution (``conv``,
``models/short_conv.py``) keeps ONE, ``conv_state`` ``[slots + 1, taps -
1, hidden]`` in the compute dtype, and no ``ssm_state``; ``read_state``,
``write_state``, the zeros at a first launch and the bytes a slot follow
the arrays that are there.

HEADS OF HALF A LANE ROW (``head_dim`` 64) lie TWO A ROW: the pool is
``[num_blocks, block_size, groups / 2, 128]``, which is the token's
contiguous ``[groups, 64]`` as it stands, so it holds 2 x groups x 64
values a token and not twice that (an array whose last dimension is 64 is
laid out, and fetched, at 128 lanes, and the walk cannot slice it:
``heads_a_row``).  ``attend`` writes through the same free reshape; the
kernel walks the pool as one of ``groups / 2`` heads of 128, each query
head's 64 values set in its own half of a row of 128 beside zeros (its
scores are then its own key head's alone) and its own half of the
128-wide output kept; the dense path reads the pool through the reshape
back.

In a stack of ONE sublayer a layer (``cfg.one_sublayer``) an expert
layer (type ``moe``) keeps NOTHING between launches: its group is
``NONE`` and its pool ``{}``, a pytree of no arrays, so the list of
pools stays one entry a layer and no program moves a byte for it.  Its
:class:`PagedKVCache` carries the step's live rows in (``valid_lens``)
and the layer's routing histogram out (``moe_counts``), which a mixer
layer of such a stack leaves None: the histogram's rows are the expert
layers.

A :class:`PagedKVCache` is what the model is handed for one step of one
layer: the pool plus the step's state (block tables, context lengths,
valid lengths) and, as STATIC data, the path that reads the pool:
``'pallas'`` (``ops/pallas/paged_attention.py``: a walk over each
slot's live pages, whose time follows what is live and not the table)
or ``'xla'`` (that file's dense reference: gather every slot's table
and mask; what runs on the CPU and on a mesh of several devices, and
what the kernel's tests compare against).  A jitted program is static
in the path without any config field; ``resolve_kernel`` is where a
requested ``auto|on|off`` becomes a path, once.  Shapes are fixed by
the pool and table geometry, so a jitted step never recompiles as
requests come and go.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.config import INT8_POOL, refusal

KERNEL_MODES = ("auto", "on", "off")
_LANES = 128


def _to_width(x: jax.Array, width: int) -> jax.Array:
    """x with zeros appended to its last dimension up to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def resolve_kernel(requested: str, one_device: bool) -> str:
    """``auto|on|off`` (``--serve_paged_kernel``, ``--serve_prefill_kernel``)
    -> ``'pallas' | 'xla'``.  The kernel runs where Pallas can (a TPU, or
    interpret mode in tests).  A Mosaic call cannot be partitioned by
    GSPMD, so ``auto`` takes it only for a program that runs on ONE
    device (the caller knows where its arrays live; traced code cannot
    see that); ``on`` insists, and a Mosaic call left in a partitioned
    program is then a lowering error, never a quiet XLA run."""
    if requested not in KERNEL_MODES:
        raise ValueError(f"paged kernel mode must be auto|on|off, got "
                         f"{requested!r}")
    from megatron_llm_tpu.ops.pallas import paged_attention as _pa

    if (requested != "off" and _pa.kernel_available()
            and (requested == "on" or one_device)):
        return "pallas"
    return "xla"


def expands_latents(kernel: str, n: int) -> bool:
    """Whether a latent pool's read of ``n`` queries a row on the resolved
    path ``kernel`` is in the EXPANDED form: a chunk (``n > 1``) on the
    kernel path (``mla_attention_prefill``).  A decode step and the dense
    fallback are absorbed.  The cache asks it to pick the read and the
    engine to count what its launches expand."""
    from megatron_llm_tpu.ops.pallas import paged_attention as _pa

    return n > 1 and kernel == "pallas" and _pa.kernel_available()


FULL, WINDOW, STATE, NONE = "full", "window", "state", "none"
# beside the groups' tables in what a prefill CHUNK is handed
# (``CachePlan.chunk_tables``): whether the chunk ends its context
LAST = "last"
# the recurrent state's dtype: an ASSUMPTION (the published config gives
# no cache dtype), float32 because it is multiplied and added to at every
# token of a request
SSM_STATE_DTYPE = jnp.float32
_GROUP_OF = {"sliding": WINDOW, "moe": NONE}
# the arrays a slot of the STATE group may hold, in the order
# ``read_state`` gives and ``write_state`` takes them; which of them a
# layer's pool has is its kind's (``init_pools``)
_STATE_ARRAYS = ("conv_state", "ssm_state", "ret_state", "ret_sum",
                 "delta_state")


def layer_groups(cfg) -> Optional[tuple]:
    """The pool group of each layer of a model with a layer type per
    layer (``FULL`` | ``WINDOW`` | ``STATE``, or ``NONE`` for an expert
    layer alone, which keeps nothing); None for a model of one type,
    which has one group."""
    if cfg.layer_types is None:
        return None
    types = (cfg.layer_type(i) for i in range(cfg.cache_layers))
    return tuple(STATE if cfg.state_layer(t) else _GROUP_OF.get(t, FULL)
                 for t in types)


def heads_a_row(cfg) -> int:
    """Key (or value) heads of one token that share a 128-lane row of the
    pool: 2 for heads of half a row (``head_dim`` 64) in pairs, else 1
    (a head is a row, or rows, of its own)."""
    return 2 if (2 * cfg.head_dim == _LANES
                 and cfg.num_query_groups % 2 == 0) else 1


def window_pages_bound(window: int, chunk: int, block_size: int) -> int:
    """The most pages a request holds in the window group: the window,
    the tokens one launch writes (``chunk``) and one page, since neither
    end of that span need lie on a page's edge."""
    return -(-(window + chunk) // block_size) + 1


def init_pools(cfg, num_blocks: int, block_size: int, dtype=None,
               quantized: bool = False,
               window_blocks: Optional[int] = None,
               num_slots: Optional[int] = None) -> List[dict]:
    """One pool a layer A PASS (``cfg.cache_layers`` of them: pass t's
    layer i holds pool ``t * num_layers + i``, and a page id names its
    tokens in all of them) for a model of config ``cfg``: keys and values in
    the compute dtype, or int8 with fp32 scales when ``quantized`` (halves
    the KV bytes a decode step reads, against bf16); with a
    sparse-attention indexer, its keys beside them (``index_pages``).  A
    layer's pool is sized by its group: ``window_blocks`` for the window
    group of a model with a layer type per layer, ``num_blocks`` for
    every other layer; a layer that carries a state holds its kind's
    arrays a slot (a state-space layer two, a gated short convolution
    one, a power retention its state and its normaliser, a gated delta
    rule its convolution's columns and its state), ``num_slots`` of them
    and the garbage row."""
    dtype = dtype or cfg.compute_jnp_dtype
    indexed = cfg.dsa_index_heads > 0
    groups = layer_groups(cfg)
    said = quantized and refusal(cfg, (INT8_POOL,))
    if said:
        raise ValueError(said)
    index_width = -(-cfg.dsa_index_head_dim // _LANES) * _LANES
    if cfg.latent_attention:
        shape = (num_blocks, block_size, latent_width(cfg))

        def latent_pool():
            pool = {"latent_pages": jnp.zeros(shape, dtype)}
            if indexed:
                pool["index_pages"] = jnp.zeros(shape[:2] + (index_width,),
                                                dtype)
            return pool

        return [latent_pool() for _ in range(cfg.cache_layers)]
    if groups is not None and WINDOW in groups and not window_blocks:
        raise ValueError("a model with sliding layers among its "
                         "layer_types needs window_blocks")
    if groups is not None and STATE in groups and not num_slots:
        raise ValueError("a model with state-space layers among its "
                         "layer_types needs num_slots")

    def state(kind):
        if kind == "retention":
            # a key-value head's state, a [value, a] tile a rotation of
            # phi, and its normaliser (models/retention.py has the layout)
            g, d = cfg.num_query_groups, cfg.head_dim
            O = cfg.retention_phi_rows // d
            return {"ret_state": jnp.zeros((num_slots + 1, g, O, d, d),
                                           SSM_STATE_DTYPE),
                    "ret_sum": jnp.zeros((num_slots + 1, g, O, d),
                                         SSM_STATE_DTYPE)}
        if kind == "conv":
            return {"conv_state": jnp.zeros(
                (num_slots + 1, cfg.conv_taps - 1, cfg.hidden_size), dtype)}
        if kind == "gated_delta":
            # the columns of [q | k | v] before the convolution, and a
            # value head's [key, value] state (models/gated_delta.py)
            return {
                "conv_state": jnp.zeros(
                    (num_slots + 1, cfg.delta_conv_taps - 1,
                     cfg.delta_conv_dim), dtype),
                "delta_state": jnp.zeros(
                    (num_slots + 1, cfg.delta_value_heads,
                     cfg.delta_key_dim, cfg.delta_value_dim),
                    SSM_STATE_DTYPE)}
        return {
            "conv_state": jnp.zeros(
                (num_slots + 1, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                dtype),
            "ssm_state": jnp.zeros(
                (num_slots + 1, cfg.mamba_n_heads, cfg.mamba_d_head,
                 cfg.mamba_d_state), SSM_STATE_DTYPE)}

    pack = 1 if quantized or indexed else heads_a_row(cfg)

    def pool(blocks):
        shape = (blocks, block_size, cfg.num_query_groups // pack,
                 cfg.head_dim * pack)
        if quantized:
            return {"k_pages_q": jnp.zeros(shape, jnp.int8),
                    "k_pages_scale": jnp.ones(shape[:3], jnp.float32),
                    "v_pages_q": jnp.zeros(shape, jnp.int8),
                    "v_pages_scale": jnp.ones(shape[:3], jnp.float32)}
        kv = {"k_pages": jnp.zeros(shape, dtype),
              "v_pages": jnp.zeros(shape, dtype)}
        if indexed:
            kv["index_pages"] = jnp.zeros(shape[:2] + (index_width,), dtype)
        return kv

    return [{} if groups and groups[i] == NONE else
            state(cfg.layer_type(i)) if groups and groups[i] == STATE else
            pool(window_blocks if groups and groups[i] == WINDOW
                 else num_blocks) for i in range(cfg.cache_layers)]


def is_state(pool: dict) -> bool:
    """Whether a layer's pool is a state-carrying layer's (arrays a slot,
    no pages)."""
    return any(name in pool for name in _STATE_ARRAYS)


def paged_pools(pools) -> List[dict]:
    """The pools that have pages: what the page programs, copy-on-write
    and ``block_bytes`` run over."""
    return [p for p in pools if p and not is_state(p)]


def with_paged(pools, paged) -> List[dict]:
    """``pools`` with its paged pools replaced by ``paged``, in order."""
    paged = iter(paged)
    return [next(paged) if p and not is_state(p) else p for p in pools]


def state_bytes_per_slot(pools) -> int:
    """Bytes one slot's recurrent state takes over the state-space
    layers of ``pools`` (0 for a model with none)."""
    return sum(math.prod(a.shape[1:]) * a.dtype.itemsize
               for p in pools if is_state(p)
               for a in jax.tree_util.tree_leaves(p))


def latent_width(cfg) -> int:
    """The width of a latent pool's row: the latent and the rotary key,
    filled up to whole lanes."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // _LANES) * _LANES


def _arrays(pool: dict):
    """(K, V, K scales, V scales) of a pool; the scales None unless int8."""
    if "k_pages_q" in pool:
        return (pool["k_pages_q"], pool["v_pages_q"],
                pool["k_pages_scale"], pool["v_pages_scale"])
    return pool["k_pages"], pool["v_pages"], None, None


def block_bytes(pools) -> int:
    """Bytes of one block across every layer's pool that has pages (of a
    model with two groups of pages: across the layers of ``pools``,
    which the caller picks by group)."""
    return sum(math.prod(a.shape[1:]) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(paged_pools(pools)))


def array_shapes(pools) -> set:
    """``(dtype name, shape)`` of every array of every layer's pool, as
    allocated ``[blocks, block_size, ...]`` and as ``attend`` writes it,
    one row a token ``[blocks * block_size, ...]``: what an instruction of
    a compiled program is held against to say that it moves the pool.
    A state-space layer's arrays are not among them: ``state_shapes``."""
    out = set()
    for a in jax.tree_util.tree_leaves(paged_pools(pools)):
        out.add((a.dtype.name, tuple(a.shape)))
        out.add((a.dtype.name, (a.shape[0] * a.shape[1],) + a.shape[2:]))
    return out


def state_shapes(pools) -> set:
    """``(dtype name, shape)`` of every array of every state-space
    layer's pool, as allocated and as a decode step writes its live rows
    (without the garbage row): the role ``ssm_state`` of a compiled
    program's instructions."""
    out = set()
    for p in pools:
        if is_state(p):
            for a in p.values():
                out.add((a.dtype.name, tuple(a.shape)))
                out.add((a.dtype.name, (a.shape[0] - 1,) + a.shape[1:]))
    return out


def fetch_page(pools, src):
    """Physical page ``src`` of every array of every layer's pool."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, src, axis=0,
                                               keepdims=False), pools)


def load_page(pools, page, dst):
    """The pools with ``page`` (what ``fetch_page`` returns) written at
    physical page ``dst``."""
    return jax.tree_util.tree_map(
        lambda a, p: jax.lax.dynamic_update_index_in_dim(a, p, dst, axis=0),
        pools, page)


def copy_page(pools, src, dst):
    """The pools with physical page ``src`` duplicated into ``dst``."""
    return load_page(pools, fetch_page(pools, src), dst)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PagedKVCache:
    """One layer's pool with one step's state.

    ``block_tables`` [b, M] int32 maps each row's logical pages to pool
    blocks (entries beyond a row's allocation are 0, the garbage block);
    ``context_lens`` [b] counts the tokens already in the cache;
    ``valid_lens`` [b] the real tokens of this call (0: an idle row,
    which writes to the garbage block and is not read for).
    ``moe_counts`` is an OUTPUT a sparse model's layer leaves for the
    engine's counters: ``[E]`` live (token, choice) assignments the
    layer's router made this step; None on the way in and for a dense
    model.  ``kernel`` is static: ``'pallas' | 'xla'``; so is ``group``,
    the layer's pool group (``FULL`` for every layer of a model of one
    type): ``block_tables`` is that group's table, and the window
    group's walk launches under its own kernel names
    (``paged_attention_decode_window``, ``paged_attention_prefill_window``)
    so that a trace tells the two kinds of attention apart.  A
    state-space layer's cache (group ``STATE``) holds its two arrays a
    slot and ``slots`` [b], each row's slot (None: row s is slot s, the
    decode step); its ``block_tables`` is not read."""

    pool: dict
    block_tables: jax.Array
    context_lens: jax.Array
    valid_lens: jax.Array
    kernel: str = dataclasses.field(metadata=dict(static=True))
    moe_counts: Optional[jax.Array] = None
    group: str = dataclasses.field(default=FULL, metadata=dict(static=True))
    slots: Optional[jax.Array] = None

    def _rows(self, a: jax.Array, fresh: jax.Array) -> jax.Array:
        """Array ``a`` of a state-space layer's pool as each row finds
        it: its slot's, or zeros where the row is ``fresh`` (its
        ``context_lens`` is 0: a request's first launch, whatever the
        slot held)."""
        b = fresh.shape[0]
        a = a[:b] if self.slots is None else a[self.slots]
        return jnp.where(fresh.reshape((b,) + (1,) * (a.ndim - 1)),
                         jnp.zeros((), a.dtype), a)

    def _put(self, a: jax.Array, val: jax.Array,
             live: jax.Array) -> jax.Array:
        """Array ``a`` of a state-space layer's pool with each ``live``
        row's ``val`` written at its slot (an idle row's, ``valid_lens``
        0, at the garbage row)."""
        b = live.shape[0]
        val = val.astype(a.dtype)
        if self.slots is None:
            # row s is slot s: one pass over the live rows
            keep = live.reshape((b,) + (1,) * (a.ndim - 1))
            return jnp.concatenate([jnp.where(keep, val, a[:b]), a[b:]])
        return a.at[jnp.where(live, self.slots, a.shape[0] - 1)].set(val)

    def read_state(self) -> tuple:
        """A state-carrying layer's arrays as each row finds them
        (``_rows``), in ``_STATE_ARRAYS``' order: a state-space layer's
        (``conv_state`` [b, d_conv - 1, conv_dim], ``ssm_state`` [b,
        heads, d_head, d_state]), a gated short convolution's
        (``conv_state`` [b, taps - 1, hidden],) alone, a power
        retention's (``ret_state`` [b, g, O, d, d], ``ret_sum`` [b, g,
        O, d]), a gated delta rule's (``conv_state`` [b, taps - 1,
        channels], ``delta_state`` [b, value heads, d_key, d_value])."""
        fresh = self.context_lens == 0
        return tuple(self._rows(self.pool[name], fresh)
                     for name in _STATE_ARRAYS if name in self.pool)

    def step_state(self, decay, dx, B, C):
        """One token of a state-space layer's recurrence on every live
        row's ``ssm_state`` (``ops/pallas/ssm_step.py`` has the operands):
        ``y`` [b, heads, d_head] float32 and the cache with the state
        WRITTEN (``write_state`` then takes the columns alone).  On the
        ``'pallas'`` path of a decode step (row s is slot s) the kernel
        updates the pool in place and moves live rows only; otherwise
        every row's state is read, advanced and put back."""
        from megatron_llm_tpu.ops.pallas import ssm_step as _ssm

        pool = self.pool["ssm_state"]
        live, fresh = self.valid_lens > 0, self.context_lens == 0
        if self.kernel == "pallas" and self.slots is None:
            y, pool = _ssm.ssm_state_step(pool, decay, dx, B, C, live, fresh)
        else:
            y, new = _ssm.dense_ssm_step(self._rows(pool, fresh), decay, dx,
                                         B, C)
            pool = self._put(pool, new, live)
        return y, dataclasses.replace(
            self, pool={**self.pool, "ssm_state": pool})

    def step_delta(self, q, k, v, g, beta):
        """One token of a gated delta-rule layer's recurrence on every
        live row's ``delta_state`` (``ops/pallas/delta_step.py`` has the
        operands): ``o`` [b, value heads, d_value] float32 and the cache
        with the state WRITTEN (``write_state`` then takes the columns
        alone).  On the ``'pallas'`` path of a decode step (row s is slot
        s) the kernel updates the pool in place and moves live rows
        only; otherwise every row's state is read, advanced and put
        back."""
        from megatron_llm_tpu.ops.pallas import delta_step as _delta

        pool = self.pool["delta_state"]
        live, fresh = self.valid_lens > 0, self.context_lens == 0
        if self.kernel == "pallas" and self.slots is None:
            o, pool = _delta.delta_state_step(pool, q, k, v, g, beta, live,
                                              fresh)
        else:
            o, new = _delta.dense_gated_delta_step(
                self._rows(pool, fresh), q, k, v, g, beta)
            pool = self._put(pool, new, live)
        return o, dataclasses.replace(
            self, pool={**self.pool, "delta_state": pool})

    def step_retention(self, q, k, v, a):
        """One token of a power-retention layer's recurrence on every
        live row's ``ret_state`` and ``ret_sum``
        (``ops/pallas/retention_step.py`` has the operands): numerators
        [b, g, r, d] and normalisers [b, g, r] in float32 and the cache
        with both arrays WRITTEN (``write_state()`` then only advances
        the lengths).  On the ``'pallas'`` path of a decode step (row s
        is slot s) the kernel updates ``ret_state`` in place and moves
        live rows only; otherwise every row's state is read, advanced
        and put back.  The normaliser, a 128th of the bytes, is XLA's on
        both."""
        from megatron_llm_tpu.ops.pallas import retention_step as _ret

        S, z = self.pool["ret_state"], self.pool["ret_sum"]
        live, fresh = self.valid_lens > 0, self.context_lens == 0
        a = a.astype(jnp.float32)
        if self.kernel == "pallas" and self.slots is None:
            num, S = _ret.retention_state_step(S, q, k, v, a, live, fresh)
            den, z_new = _ret.dense_sum_step(self._rows(z, fresh), q, k, a)
        else:
            num, den, S_new, z_new = _ret.dense_retention_step(
                self._rows(S, fresh), self._rows(z, fresh), q, k, v, a)
            S = self._put(S, S_new, live)
        return num, den, dataclasses.replace(
            self, pool={**self.pool, "ret_state": S,
                        "ret_sum": self._put(z, z_new, live)})

    def chunk_retention(self, q, k, v, a, cdtype):
        """A chunk of a power-retention layer's recurrence on the
        ``'pallas'`` path (``ops/pallas/retention_chunk.py`` has the
        operands): ONE kernel that walks the chunk's blocks over each
        row's ``ret_state`` where it lies in the pool and forms ``phi``
        in VMEM.  Numerators [b, n, g, r, d] and normalisers [b, n, g,
        r] in float32 and the cache with both arrays WRITTEN
        (``write_state()`` then only advances the lengths).  The
        normaliser, a 128th of the bytes, goes in as ``read_state``
        gives the rows' (the state's own rows are not asked for, so
        nothing gathers them) and is put back by ``_put``, as around the
        step's kernel.  On the ``'xla'`` path the mixer runs
        ``retention.retention_chunk`` between ``read_state`` and
        ``write_state``."""
        from megatron_llm_tpu.ops.pallas import retention_chunk as _ret

        slots = (jnp.arange(q.shape[0], dtype=jnp.int32)
                 if self.slots is None else self.slots)
        num, den, S, z_new = _ret.retention_state_chunk(
            self.pool["ret_state"], self.read_state()[1], q, k, v, a, slots,
            self.valid_lens, self.context_lens == 0, cdtype)
        return num, den, dataclasses.replace(
            self, pool={**self.pool, "ret_state": S, "ret_sum": self._put(
                self.pool["ret_sum"], z_new, self.valid_lens > 0)})

    def write_state(self, *arrays):
        """The cache as a state-carrying layer's call leaves it: each
        live row's ``arrays`` (in ``read_state``'s order) written at its
        slot (``_put``; one left out or None stays as it is:
        ``step_state`` has written ``ssm_state``, ``step_delta``
        ``delta_state``, ``step_retention`` and ``chunk_retention`` both
        of their arrays), ``context_lens`` advanced."""
        live = self.valid_lens > 0
        pool = dict(self.pool)
        names = [name for name in _STATE_ARRAYS if name in self.pool]
        for name, val in zip(names, arrays):
            if val is not None:
                pool[name] = self._put(self.pool[name], val, live)
        return dataclasses.replace(
            self, pool=pool, context_lens=self.context_lens + self.valid_lens)

    def live(self, n: int) -> jax.Array:
        """[b, n] bool: which of this call's n tokens a row are real."""
        return jnp.arange(n)[None, :] < self.valid_lens[:, None]

    def attend(self, q: jax.Array, k: jax.Array, v: jax.Array,
               sliding_window: Optional[int], index=None,
               scale: Optional[float] = None):
        """Write this call's keys and values ``[b, n, g, d]`` at
        ``context_lens ..``, then attend ``q`` [b, n, nh, d] over the
        row's history and the chunk's own causal prefix.  Returns the
        context ``[b, n, nh, d]`` and the cache as the step leaves it
        (``context_lens`` advanced by ``valid_lens``).  Rows past
        ``valid_lens`` are garbage in, garbage out on either path.

        ``index`` (a pool with ``index_pages`` needs it, no other takes
        it): the sparse-attention indexer's ``(queries [b, n, Hi, di],
        key [b, n, di], head weights [b, n, Hi], topk)``.  The key is
        written beside K and V, and each query then attends only the
        ``topk`` keys its indexer scores highest over the same range
        (``ops/dsa.py`` says exactly which).  ``scale`` multiplies the
        scores (None: ``1 / sqrt(d)``)."""
        from megatron_llm_tpu.ops.pallas import paged_attention as _pa

        if (index is not None) != ("index_pages" in self.pool):
            raise ValueError("a pool with index_pages, and only such a "
                             "pool, is attended through an indexer")

        bt, ctx_lens, vlen = (self.block_tables, self.context_lens,
                              self.valid_lens)
        n, d = k.shape[1], k.shape[3]
        quantized = "k_pages_q" in self.pool
        # heads of half a lane row lie two a row of the pool (module
        # docstring): a token's [g, d] IS its [g / pack, pack * d]
        pack = 1 if quantized else self.pool["k_pages"].shape[-1] // d
        if pack > 1:
            k, v = (a.reshape(a.shape[:2] + (-1, pack * d)) for a in (k, v))
        if quantized:
            from megatron_llm_tpu.quantization import absmax_quantize_int8

            kq, ks = absmax_quantize_int8(k, axis=-1)
            vq, vs = absmax_quantize_int8(v, axis=-1)
            writes = {"k_pages_q": kq, "k_pages_scale": ks,
                      "v_pages_q": vq, "v_pages_scale": vs}
        else:
            writes = {"k_pages": k, "v_pages": v}
        if index is not None:
            writes["index_pages"] = _to_width(
                index[1], self.pool["index_pages"].shape[-1])
        pool = self._write(writes, n)
        kp, vp, k_scales, v_scales = _arrays(pool)
        if scale is None:
            scale = 1.0 / math.sqrt(d)
        if index is not None:
            ctx = self._attend_selected(q, pool, index, scale)
        elif self.kernel == "pallas":
            # the chunk's own K/V were just scattered, so the kernel's
            # causal walk covers history AND the in-flight chunk
            kw = dict(valid_lens=vlen, k_scales=k_scales, v_scales=v_scales,
                      softmax_scale=scale, sliding_window=sliding_window,
                      name_suffix="_window" if self.group == WINDOW else "")
            if pack > 1:
                q = _in_own_part(q, kp.shape[2], pack)
            if n == 1:
                ctx = _pa.paged_attention_decode(
                    q[:, 0], kp, vp, bt, ctx_lens, **kw)[:, None]
            else:
                ctx = _pa.paged_attention_prefill(
                    q, kp, vp, bt, ctx_lens, **kw)
            if pack > 1:
                ctx = _own_part(ctx, kp.shape[2], pack)
        else:
            if pack > 1:
                # the pool as [blocks, block_size, g, d]: the same bytes
                kp, vp = (a.reshape(a.shape[:2] + (-1, d)) for a in (kp, vp))
            ctx = _pa.dense_paged_attention(
                q, kp, vp, bt, ctx_lens, vlen, k_scales, v_scales,
                scale, sliding_window)
        return ctx, dataclasses.replace(self, pool=pool,
                                        context_lens=ctx_lens + vlen)


    def _write(self, writes: dict, n: int) -> dict:
        """The pool with this call's rows ``writes[name]`` [b, n, ...]
        scattered at ``context_lens ..`` of each row's table, under the
        scope ``kv_write``.  The scatter is at (page, row) of the pool
        in its own shape: through a one-row-a-token reshape the TPU's
        compiler moved a pool array it could have written in place
        through fast memory and back, whole (PERF.md section 6, PR 41)."""
        bt, ctx_lens, vlen = (self.block_tables, self.context_lens,
                              self.valid_lens)
        P, bs = next(iter(self.pool.values())).shape[:2]
        M = bt.shape[1]
        j = jnp.arange(n)[None, :]
        pos = ctx_lens[:, None] + j                          # [b, n] abs pos
        blk = jnp.take_along_axis(bt, jnp.clip(pos // bs, 0, M - 1), axis=1)
        # padded / inactive tokens (not live) land in garbage block 0
        # (duplicate scatter indices there are fine)
        dest = jnp.where(j < vlen[:, None], blk * bs + pos % bs, pos % bs)
        dest = jnp.clip(dest, 0, P * bs - 1)
        pool = {}
        with jax.named_scope("kv_write"):
            for name, val in writes.items():
                a = self.pool[name]
                pool[name] = a.at[dest // bs, dest % bs].set(val)
        return pool

    def expands_latents(self, n: int) -> bool:
        """Whether ``attend_latent`` reads ``n`` queries a row in the
        EXPANDED form (:func:`expands_latents`)."""
        return expands_latents(self.kernel, n)

    def attend_latent(self, q_nope: jax.Array, q_rope: jax.Array,
                      latent: jax.Array, k_rope: jax.Array, scale: float,
                      kv_up: Optional[jax.Array] = None, index=None):
        """A latent pool's ``attend``: write this call's rows
        ``[latent [b, n, r] ; k_rope [b, n, dr] ; zeros]`` at
        ``context_lens ..``, then attend over the row's history and the
        chunk's own causal prefix, in one of two forms (the caller asks
        ``expands_latents`` which):

        * ABSORBED (``kv_up`` None: a decode step, ``mla_attention_decode``,
          and the dense fallback): ``q_nope`` is the query with the
          up-projection's key half folded in ``[b, n, nh, r]``; every
          head attends ONE key a token, the row, whose first ``r`` columns
          are also its value, and the context comes back IN THE LATENT
          ``[b, n, nh, r]`` (the caller applies the value half).
        * EXPANDED (``kv_up`` [r, nh, dn + dv]: a chunk on the kernel
          path, ``mla_attention_prefill``): ``q_nope`` [b, n, nh, dn] as
          the model made it; the kernel expands each block of latents
          into per-head keys and values in VMEM and the context comes
          back per head ``[b, n, nh, dv]``.

        Either reads a live page once (a head group).  Returns the context
        and the cache as the step leaves it.

        ``index`` (a pool with ``index_pages`` needs it, no other takes
        it; as ``attend``'s): the indexer's key is written beside the
        row, at the same place of ``index_pages``, and each query then
        attends only the ``topk`` rows its indexer scores highest, in
        the same two forms (``_attend_latent_selected``)."""
        from megatron_llm_tpu.ops.pallas import paged_attention as _pa

        if (index is not None) != ("index_pages" in self.pool):
            raise ValueError("a pool with index_pages, and only such a "
                             "pool, is attended through an indexer")
        pages = self.pool["latent_pages"]
        n, r, W = latent.shape[1], latent.shape[2], pages.shape[-1]
        assert (kv_up is not None) == self.expands_latents(n)
        row = _to_width(jnp.concatenate([latent, k_rope], axis=-1), W)
        writes = {"latent_pages": row.astype(pages.dtype)}
        if index is not None:
            ip = self.pool["index_pages"]
            writes["index_pages"] = _to_width(index[1],
                                              ip.shape[-1]).astype(ip.dtype)
        pool = self._write(writes, n)
        args = (pool["latent_pages"], self.block_tables, self.context_lens)
        if index is not None:
            ctx = self._attend_latent_selected(q_nope, q_rope, pool, index,
                                               scale, r, kv_up)
        elif kv_up is not None:
            ctx = _pa.latent_attention_prefill(
                q_nope, q_rope, kv_up, *args, valid_lens=self.valid_lens,
                softmax_scale=scale)
        else:
            q = _to_width(jnp.concatenate([q_nope, q_rope], axis=-1), W)
            if self.kernel != "pallas" or n > 1:
                ctx = _pa.dense_latent_attention(
                    q, *args, self.valid_lens, scale, r)
            else:
                ctx = _pa.latent_attention_decode(
                    q[:, 0], *args, valid_lens=self.valid_lens,
                    value_width=r, softmax_scale=scale)[:, None]
        return ctx, dataclasses.replace(
            self, pool=pool, context_lens=self.context_lens + self.valid_lens)

    def _attend_latent_selected(self, q_nope, q_rope, pool, index, scale,
                                r, kv_up):
        """``attend_latent``'s read under an indexer's choice: scores
        over the row's live pages of ``index_pages``, the choice, and
        latent attention over the chosen rows: the kernels' two forms
        (a decode step absorbed, ``mla_attention_sparse_decode``; a
        chunk expanded in its kernel, ``mla_attention_prefill_masked``),
        or the dense path, absorbed: every row's table gathered (live
        pages only) and masked."""
        iq, _, iw, topk = index
        pages, ip = pool["latent_pages"], pool["index_pages"]
        iq = _to_width(iq, ip.shape[-1])
        bt, ctx_lens, vlen = (self.block_tables, self.context_lens,
                              self.valid_lens)
        S, n = q_nope.shape[:2]
        W = pages.shape[-1]
        # the chunk's kernel takes the queries as the model made them;
        # every other read the absorbed ones at the pool's row width
        q = (q_nope if kv_up is not None else
             _to_width(jnp.concatenate([q_nope, q_rope], axis=-1), W))
        if self.kernel == "pallas" and (kv_up is not None or n == 1):
            from megatron_llm_tpu.ops.pallas import dsa_attention as _dsa

            return _dsa.paged_selected_latent_attention(
                q, q_rope, kv_up, iq, iw, pages, ip, bt, ctx_lens, vlen,
                topk=topk, softmax_scale=scale, value_width=r)
        from megatron_llm_tpu.ops import dsa as _dsa

        assert kv_up is None
        bs, M = pages.shape[1], bt.shape[1]
        live = ctx_lens + vlen
        bt = jnp.where(jnp.arange(M)[None, :] * bs < live[:, None], bt, 0)
        rows = pages[bt].reshape(S, M * bs, 1, W)
        pos = ctx_lens[:, None] + jnp.arange(n)[None, :]
        # a row is the key and, in its first ``r`` columns, the value
        return _dsa.selected_attention(
            q, rows, rows, iq, ip[bt].reshape(S, M * bs, -1), iw, pos, topk,
            scale)[..., :r]

    def _attend_selected(self, q, pool, index, scale):
        """``attend``'s read for a pool with an indexer: scores over the
        row's live pages of ``index_pages``, the choice, attention over
        the chosen keys.  The kernels walk the pages; the dense path
        gathers every row's table (live pages only) and masks."""
        iq, _, iw, topk = index
        iq = _to_width(iq, pool["index_pages"].shape[-1])
        bt, ctx_lens, vlen = (self.block_tables, self.context_lens,
                              self.valid_lens)
        kp, vp, ip = pool["k_pages"], pool["v_pages"], pool["index_pages"]
        if self.kernel == "pallas":
            from megatron_llm_tpu.ops.pallas import dsa_attention as _dsa

            return _dsa.paged_selected_attention(
                q, iq, iw, kp, vp, ip, bt, ctx_lens, vlen, topk=topk,
                softmax_scale=scale)
        from megatron_llm_tpu.ops import dsa as _dsa

        S, n = q.shape[:2]
        bs, M = kp.shape[1], bt.shape[1]
        live = ctx_lens + vlen
        bt = jnp.where(jnp.arange(M)[None, :] * bs < live[:, None], bt, 0)

        def gathered(pages):
            return pages[bt].reshape((S, M * bs) + pages.shape[2:])

        pos = ctx_lens[:, None] + jnp.arange(n)[None, :]
        return _dsa.selected_attention(
            q, gathered(kp), gathered(vp), iq, gathered(ip), iw, pos, topk,
            scale)


def _in_own_part(q: jax.Array, rows: int, pack: int) -> jax.Array:
    """Queries ``[b, n, nh, d]`` for a pool whose ``rows`` rows a token
    hold ``pack`` key heads each: ``[b, n, nh, pack * d]``, a head's
    values in the part of the row its own key head lies in (key head
    ``gh`` is part ``gh % pack`` of row ``gh // pack``) and zeros in the
    others, so that its product with a row is its own key head's alone.
    The walk then takes the pool as ``rows`` heads of ``pack * d``."""
    b, n, nh, d = q.shape
    qpg = nh // (rows * pack)
    q = q.reshape(b, n, rows, pack, qpg, 1, d)
    own = jnp.eye(pack, dtype=q.dtype).reshape(1, 1, 1, pack, 1, pack, 1)
    return (q * own).reshape(b, n, nh, pack * d)


def _own_part(ctx: jax.Array, rows: int, pack: int) -> jax.Array:
    """What ``_in_own_part``'s queries attended, ``[b, n, nh, pack * d]``
    (each head against ``pack`` value heads side by side), cut to the
    head's own value head: ``[b, n, nh, d]``."""
    b, n, nh, wide = ctx.shape
    qpg = nh // (rows * pack)
    ctx = ctx.reshape(b, n, rows, pack, qpg, pack, wide // pack)
    return jnp.stack([ctx[:, :, :, p, :, p] for p in range(pack)],
                     axis=3).reshape(b, n, nh, wide // pack)


def step_caches(pools, block_tables, context_lens, valid_lens,
                kernel: Optional[str] = None,
                groups: Optional[tuple] = None) -> List[PagedKVCache]:
    """Every layer's cache for one step over ``pools``.  ``kernel`` None
    is what ``auto`` means for a program on one device: the kernel where
    it can run (whoever jits for several devices says ``'xla'``).  With
    ``groups`` (``layer_groups``) ``block_tables`` is a dict of a table a
    group and each layer carries its own group's.  A state-space layer
    reads no table: its entry ``block_tables[STATE]``, where there is
    one, is ``[b]`` each row's SLOT (a prefill chunk's; a decode step has
    none: row s is slot s).  An expert layer alone (``NONE``) reads
    none either."""
    kernel = kernel or resolve_kernel("auto", one_device=True)
    if groups is None:
        return [PagedKVCache(p, block_tables, context_lens, valid_lens,
                             kernel=kernel) for p in pools]
    return [PagedKVCache(p, block_tables.get(FULL) if g in (STATE, NONE)
                         else block_tables[g],
                         context_lens, valid_lens, kernel=kernel, group=g,
                         slots=block_tables.get(STATE) if g == STATE
                         else None)
            for p, g in zip(pools, groups)]


@dataclasses.dataclass(frozen=True)
class CachePlan:
    """What the serving engine holds of its model's cache, worked out
    ONCE (``plan``): this module's functions given their arguments.  The
    engine asks it for the pools, the tables a program takes and a
    launch's account, and knows nothing else of what a model keeps
    between launches."""

    cfg: Any
    block_size: int
    num_slots: int
    # the engine's resolved paths (``resolve_kernel``) of a chunk and of
    # a decode step: what a launch counts depends on which one ran
    prefill_kernel: str
    paged_kernel: str
    # ``layer_groups(cfg)``: what ``step_caches`` takes
    groups: Optional[tuple]
    # what ``serving/kv_blocks.py::WindowGroup`` is built with, its pool's
    # blocks first (every slot at its bound); None without a window group
    window: Optional[tuple]
    # a block's bytes over the layers of the full and the window group,
    # and a slot's state over the layers that carry one
    group_block_bytes: tuple
    state_bytes_per_slot: int
    # learned sparse attention: the keys of a block its choice counts in
    # and the blocks of a slot's table (0 and 0 without an indexer)
    dsa_block_keys: int
    dsa_table_blocks: int
    # a layer whose pool holds arrays (an expert layer alone holds none):
    # what a launch is waited for by
    first_pool: int = 0
    # the layer type of each layer of the STATE group, in order: what a
    # launch's counters of state are counted by
    state_kinds: tuple = ()
    # whether the pools are int8 with fp32 scales: what ``init_pools``
    # makes, and what a launch's walks multiply in
    int8_pool: bool = False

    @property
    def paged(self) -> bool:
        """Whether some layer keeps pages (a model of one type always; a
        typed stack whose every layer carries a state, or nothing, has
        none): what admission, the page programs and the tables ask."""
        return self.groups is None or any(
            g in (FULL, WINDOW) for g in self.groups)

    def init_pools(self, num_blocks: int):
        return init_pools(self.cfg, num_blocks, self.block_size,
                          quantized=self.int8_pool, num_slots=self.num_slots,
                          window_blocks=self.window and self.window[0])

    def tables(self, blocks, rows=slice(None)):
        """What a program takes as ``block_tables``: rows ``rows`` of the
        slots' table of the block manager ``blocks``, or of each group's
        where there are groups."""
        if self.groups is None:
            return blocks.tables[rows].copy()
        # a table a group that has pages: none is built that nobody reads
        tables = {FULL: blocks.tables[rows].copy()} if self.paged else {}
        if blocks.window is not None:
            tables[WINDOW] = blocks.window.tables[rows].copy()
        if STATE in self.groups and rows != slice(None):
            # a state-space layer's "table": each row's slot (a decode
            # step takes every slot, row s is slot s, and carries none)
            tables[STATE] = np.arange(self.num_slots, dtype=np.int32)[rows]
        return tables

    def chunk_tables(self, blocks, slot: int, last: bool) -> dict:
        """What a prefill CHUNK of slot ``slot`` takes as ``block_tables``:
        the slot's row of each group's table under the group's name (a
        model of one type: ``{FULL: table}``; ``STATE`` the slot), and
        under ``LAST`` a 0-d bool, whether the chunk ends its request's
        context: the one chunk whose logits somebody reads."""
        tables = self.tables(blocks, slice(slot, slot + 1))
        if self.groups is None:
            tables = {FULL: tables}
        return {**tables, LAST: np.bool_(last)}

    def chunk_given(self, handed: dict) -> tuple:
        """(what ``step_caches`` takes as ``block_tables``, whether the
        chunk ends its context) of what ``chunk_tables`` built, inside
        the chunk's program."""
        tables = {g: t for g, t in handed.items() if g != LAST}
        return (tables[FULL] if self.groups is None else tables,
                handed[LAST])

    def account(self, d, context_lens, valid_lens, n: int,
                admitted: int) -> None:
        """The cache's counters of one launch on its record ``d``
        (``serving/loop_profiler.py``: ``DSA_FIELDS``, ``MLA_FIELDS``,
        ``SSM_FIELDS``, ``CONV_FIELDS``, ``RETENTION_FIELDS``,
        ``DELTA_FIELDS``, ``WALK_FIELDS``, ``LOOP_FIELDS``), from the host arrays its
        program is handed: each row's ``context_lens`` and ``valid_lens``
        (0: an idle row) of ``n`` queries a row; ``admitted``: the
        requests that hold a slot.  After the walks, returns at once for
        a model with no other mechanism."""
        cfg, layers = self.cfg, self.cfg.cache_layers
        if cfg.loop_steps > 1:
            # a looped stack: a layer's run a pass a live row
            d.loop_layer_runs = layers * int((valid_lens > 0).sum())
        if self.paged and not cfg.latent_attention:
            # a live row's walk a layer that keeps pages of K and V; in
            # the pool's dtype where the kernel runs (a chunk and the
            # verify step on one path, a decode step on the other) and
            # its own test of the two dtypes says so
            from megatron_llm_tpu.ops.pallas.paged_attention import (
                native_operands)
            walked = layers if self.groups is None else sum(
                g in (FULL, WINDOW) for g in self.groups)
            d.walks = walked * int((valid_lens > 0).sum())
            kernel = (self.paged_kernel if d.kind == "decode"
                      else self.prefill_kernel)
            if kernel == "pallas" and native_operands(
                    jnp.int8 if self.int8_pool else cfg.compute_jnp_dtype,
                    cfg.compute_jnp_dtype):
                d.walks_native = d.walks
        state_layers, conv_layers, ret_layers, delta_layers = (
            self.state_kinds.count(k)
            for k in ("mamba", "conv", "retention", "gated_delta"))
        if not (cfg.latent_attention or self.dsa_block_keys
                or self.state_kinds):
            return
        live = valid_lens > 0
        ctx, val = context_lens[live], valid_lens[live]
        if self.state_kinds:
            # the state group's bytes, whatever the kind that holds them
            d.ssm_state_bytes_held = admitted * self.state_bytes_per_slot
        if conv_layers:
            d.conv_rows_live = conv_layers * len(val)
            d.conv_tokens = conv_layers * int(val.sum())
        if ret_layers:
            d.retention_rows_live = ret_layers * len(val)
            if d.kind != "prefill":
                # as a state-space layer's: the kernel moves the live
                # rows' state, the XLA step every slot's
                d.retention_rows_moved = (
                    d.retention_rows_live if self.paged_kernel == "pallas"
                    else ret_layers * (self.num_slots + 1))
            d.retention_tokens = ret_layers * int(val.sum())
            if d.kind == "prefill" and self.prefill_kernel == "pallas":
                # the chunk ran in ops/pallas/retention_chunk.py's kernel
                d.retention_chunk_tokens_kernel = d.retention_tokens
        if delta_layers:
            d.delta_rows_live = delta_layers * len(val)
            if d.kind != "prefill":
                # as a state-space layer's: the kernel moves the live
                # rows' state, the XLA step every slot's
                d.delta_rows_moved = (
                    d.delta_rows_live if self.paged_kernel == "pallas"
                    else delta_layers * (self.num_slots + 1))
            d.delta_tokens = delta_layers * int(val.sum())
            if d.kind == "prefill" and self.prefill_kernel == "pallas":
                # the chunk ran in ops/pallas/delta_chunk.py's kernel
                d.delta_chunk_tokens_kernel = d.delta_tokens
        if state_layers:
            d.ssm_rows_live = state_layers * len(val)
            if d.kind != "prefill":
                # the step's kernel moves the live rows' state, the XLA
                # step every slot's and the garbage row's
                d.ssm_rows_moved = (
                    d.ssm_rows_live if self.paged_kernel == "pallas"
                    else state_layers * (self.num_slots + 1))
            d.ssm_tokens = state_layers * int(val.sum())
        if not (cfg.latent_attention or self.dsa_block_keys):
            return
        # for each live query the keys it sees: positions 0..its own
        first = np.cumsum(val) - val
        sees = np.repeat(ctx + 1 - first, val) + np.arange(val.sum())
        if cfg.latent_attention:
            # the launch's kind says which count they are: a chunk's
            # (query, key) pairs, a decode step's live keys
            field = "mla_pairs" if d.kind == "prefill" else "mla_keys_live"
            setattr(d, field, layers * int(sees.sum()))
            if expands_latents(self.prefill_kernel, n):
                d.mla_latents_expanded = layers * int((ctx + val).sum())
        if self.dsa_block_keys:
            from megatron_llm_tpu.ops.pallas import dsa_attention as _dsa

            steps = _dsa.select_blocks(context_lens, valid_lens, n,
                                       self.dsa_block_keys, xp=np)
            d.dsa_keys_live = layers * int(sees.sum())
            d.dsa_keys_selected = layers * int(
                sees.clip(max=cfg.dsa_topk).sum())
            d.dsa_select_blocks_counted = layers * int(steps.sum())
            d.dsa_select_blocks_table = (layers * steps.size
                                         * self.dsa_table_blocks)

    def account_routing(self, d, counts) -> None:
        """A sparse model's launch on its record (``MOE_FIELDS``) from
        ``counts`` [layers, E], the histogram of live assignments over
        the experts the router scores as the program returned it (None:
        a dense model)."""
        if counts is None:
            return
        cfg, held = self.cfg, counts
        if cfg.holds_a_share:
            first = cfg.moe_experts_first
            held = counts[:, first:first + cfg.num_experts]
        d.moe_assignments = int(counts.sum())
        d.moe_assignments_held = int(held.sum())
        d.moe_experts_touched = int((counts > 0).sum())
        d.moe_experts_touched_held = int((held > 0).sum())
        d.moe_expert_slots = int(counts.size)
        d.moe_busiest_expert_assignments = int(counts.max(axis=1).sum())


def plan(cfg, block_size: int, num_slots: int, max_blocks_per_slot: int,
         prefill_chunk: int, prefill_kernel: str,
         paged_kernel: str, int8_pool: bool = False) -> CachePlan:
    """The :class:`CachePlan` of a model of config ``cfg`` served from
    pages of ``block_size`` tokens, ``num_slots`` slots of
    ``max_blocks_per_slot`` pages and chunks of ``prefill_chunk``, the
    chunk on the resolved path ``prefill_kernel`` and the decode step on
    ``paged_kernel``; ``int8_pool``: keys and values kept in int8."""
    groups = layer_groups(cfg)
    window, dsa_block_keys, dsa_table_blocks = None, 0, 0
    if groups is not None and WINDOW in groups:
        size = int(cfg.sliding_window_size)
        bound = window_pages_bound(size, prefill_chunk, block_size)
        window = (num_slots * min(bound, max_blocks_per_slot) + 1,
                  block_size, num_slots, max_blocks_per_slot, size, bound)
    if cfg.dsa_index_heads > 0:
        from megatron_llm_tpu.ops.pallas import dsa_attention as _dsa

        dsa_block_keys = (
            _dsa.latent_block_keys(block_size, max_blocks_per_slot)
            if cfg.latent_attention else
            _dsa.block_keys(block_size, cfg.num_query_groups, cfg.head_dim,
                            cfg.compute_jnp_dtype, max_blocks_per_slot))
        dsa_table_blocks = -(-max_blocks_per_slot * block_size
                             // dsa_block_keys)
    # the pools' shapes, nothing allocated: a block's and a slot's bytes
    # do not depend on how many blocks there are
    pools = jax.eval_shape(lambda: init_pools(
        cfg, 2, block_size, window_blocks=2, num_slots=num_slots))
    return CachePlan(
        cfg, block_size, num_slots, prefill_kernel, paged_kernel, groups,
        window,
        tuple(block_bytes([p for p, g in zip(pools, groups or ())
                           if g == which]) for which in (FULL, WINDOW)),
        state_bytes_per_slot(pools), dsa_block_keys, dsa_table_blocks,
        next((i for i, g in enumerate(groups or ()) if g != NONE), 0),
        tuple(cfg.layer_type(i) for i, g in enumerate(groups or ())
              if g == STATE), int8_pool)

def pools_of(caches: List[PagedKVCache]) -> List[dict]:
    """The pools as a step left them."""
    return [c.pool for c in caches]


def routing_of(caches: List[PagedKVCache]) -> Optional[jax.Array]:
    """[sparse layers, E] int32 live assignments of a sparse model's
    step (its leading dense layers leave none); None for a dense model."""
    counts = [c.moe_counts for c in caches if c.moe_counts is not None]
    return jnp.stack(counts) if counts else None
