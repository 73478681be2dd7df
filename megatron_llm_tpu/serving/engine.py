"""Continuous-batching inference engine.

One background thread drives two jitted, fixed-shape device programs over
a single paged KV pool (``ops/paged_kv.py`` owns its layout, its reads
and writes and which kernel reads it; this module holds the pools as an
opaque pytree):

* ``decode_step`` — ``[num_slots]`` rows, one token each.  Every live
  request occupies a slot; empty slots ride along masked (their KV
  writes land in the garbage block).  All sampling knobs, block tables,
  lengths and PRNG keys are *traced* inputs, so requests join and leave
  the batch with zero recompiles — the continuous-batching property.
* ``verify_step`` — the speculative replacement for ``decode_step``
  when ``EngineConfig.speculative`` is on: a single fixed-shape
  ``[num_slots, draft_k + 1]`` forward that verifies host-proposed
  draft tokens (serving/drafter.py prompt-lookup) for every slot at
  once.  It rides the same paged pool through the scatter-before-read
  prefill path (n = K+1), with per-slot draft tokens and valid counts as
  traced inputs — a slot with no usable draft degenerates to a masked
  plain decode row, so mixed drafting/non-drafting/sampled batches
  stay zero-recompile.  Verification is exact-greedy (accepted tokens
  are token-identical to the plain path by construction); host accept
  logic advances each slot 1..K+1 tokens and rolls the context cursor
  back over rejected drafts (pages are per-slot append-only, so
  rollback is a cursor decrement — the garbage-redirect scatter
  tolerates the re-writes).
* ``prefill_step`` — ``[1, prefill_chunk]`` tokens of one request's
  prompt.  Chunking fixes the shape (one compile for any prompt length)
  and bounds how long a long prompt can stall decode: the scheduler
  strictly alternates chunks with decode steps.  The output head runs
  once a request and for one row: the host says with the chunk's tables
  (``CachePlan.chunk_tables``) whether the chunk ends its context, and
  only that chunk multiplies its last live row by the head; every other
  chunk's logits are zeros nobody reads.

Steady state is exactly these two programs plus a ``[1, V]`` first-token
sampler; ``warmup()`` compiles all three, after which
``tracing.RecompileDetector.mark_steady()`` holds (asserted in
tests/test_serving_engine.py).

Host/device split: the host's numpy arrays are the truth of every
per-slot value the scheduler reads (last tokens, context lengths, the
sampling knobs, the key a slot was given), and per-slot updates on
admission are numpy row writes: a stray
``device_array.at[python_int].set()`` or ``array[slot:slot+1]`` would
compile a fresh tiny executable per distinct slot index and trip the
recompile detector.  What changes every step (last tokens, context
lengths, the tables, the live mask) is handed to the programs as host
arrays, whole.  What changes only when the HOST writes it lives on the
device between launches (``_EngineState``): the five sampling arrays,
uploaded again only after a write made the device's copy stale, and the
PRNG key chain, which the decode and verify programs advance where it
lies and give back; a key the host itself sets (an admission's seed,
what sampling a first token left) reaches the chain as that one row's
update inside the next launch.  A launch's results set out for the host
together and the host waits for them once (``_read``).

Who owns the pool: the programs of a decode step (``decode_step``,
``verify_step``) take the pool DONATED and write it in place, so a step
copies no array of it; when the launch returns, the arrays it was given
are deleted and ``st.pages`` is the pool it gave back (``_launch_step``).
The chunk and the page programs are lent the pool.  ``st.pages``
therefore belongs to the engine's thread: readers off it (the host
tier's spill, ``program_tables``) go through the state's ``pool_lock``
and keep no array, and a launch that raises holding the pool ends in
``restart()``.

Resilience (serving/resilience.py; docs/guide/fault_tolerance.md):

* **Non-finite sentinel** — the decode step and first-token sampler
  additionally return per-slot ``isfinite(logits).all()`` flags.  They
  ride the same compiled programs and are fetched with the sampled
  tokens, so the check is free of recompiles and extra dispatches; a
  poisoned slot is evicted with ``finish_reason="nonfinite"`` while its
  batch-mates keep decoding untouched.
* **In-process restart** — all restartable state (block manager,
  scheduler, KV pages, per-slot arrays) lives in one ``_EngineState``
  object.  ``restart()`` swaps in a fresh state of identical shapes
  (every jitted program cache-hits — no recompile) and abandons the old
  one to the wedged thread, which can only scribble on garbage; requests
  that never produced a byte requeue at the queue head, mid-stream ones
  fail cleanly.  The ``EngineWatchdog`` triggers this when no dispatch
  completes within ``watchdog_secs`` while work is pending.
* **Pool-pressure preemption** — when admission stalls on *blocks* (a
  deliberately oversubscribed ``num_blocks`` pool) while a slot is
  free, the scheduler evicts a strictly-larger running request back to
  the queue head (pages released and prefix-registered, generated
  tokens kept) so the head can run; re-admission prefills over
  ``Request.context_tokens()`` and greedy continuations are
  token-identical.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from megatron_llm_tpu import config as model_config
from megatron_llm_tpu import hlo_collectives, telemetry, tracing
from megatron_llm_tpu.models.language_model import (
    language_model_forward,
    lm_head_logits,
)
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import grouped_matmul
from megatron_llm_tpu.serving.cache_observatory import CacheObservatory
from megatron_llm_tpu.serving.drafter import draft_budget, lookup_draft
from megatron_llm_tpu.serving.kv_blocks import (
    BlockManager,
    WindowGroup,
    derive_num_blocks,
)
from megatron_llm_tpu.serving.request import (
    FINISH_ABORTED,
    FINISH_DEADLINE,
    FINISH_ERROR,
    FINISH_LENGTH,
    FINISH_NONFINITE,
    FINISH_STOP,
    Request,
    RequestQueue,
    RequestState,
    SamplingParams,
)
from megatron_llm_tpu.serving.loop_profiler import (
    DispatchRecord,
    LoopProfiler,
    RequestSpan,
)
from megatron_llm_tpu.serving.resilience import (
    EngineWatchdog,
    ServingFaultInjector,
)
from megatron_llm_tpu.serving.scheduler import Scheduler
from megatron_llm_tpu.text_generation.sampling import (
    NEG_INF,
    rows_asking,
    sample_batched,
)


@dataclass
class EngineConfig:
    num_slots: int = 8              # decode batch rows
    block_size: int = 16            # tokens per KV page
    num_blocks: int = 0             # 0 = full per-slot backing (no oversub)
    max_model_len: int = 0          # 0 = model max_position_embeddings
    prefill_chunk: int = 64         # prompt tokens per prefill call
    max_queue_depth: int = 64       # admission control (HTTP 429 beyond)
    default_deadline_secs: float = 120.0  # 0 = no deadline
    int8_kv_cache: bool = False
    prefix_cache: bool = True       # share KV pages across equal prefixes
    # which path reads the pool, auto|on|off, for the decode program
    # (--serve_paged_kernel) and for the [1, C] chunked-prefill and the
    # verify programs (--serve_prefill_kernel).  Resolved once at
    # __init__ by paged_kv.resolve_kernel; the resolved paths are
    # stats()['paged_kernel'] and stats()['prefill_kernel'].
    paged_kernel: str = "auto"
    prefill_kernel: str = "auto"
    # in-engine speculative decoding (--serve_speculative /
    # --serve_draft_k): host-side prompt-lookup drafting + a fixed-shape
    # [S, K+1] exact-greedy verify step replacing the plain decode
    # program.  Resolved ONCE at __init__ (the verify program's width is
    # a compiled shape) and reported as stats()['speculative'] /
    # stats()['draft_k'].  Sampled-temperature slots draft K=0 and
    # decode normally inside the same program.
    speculative: bool = False
    draft_k: int = 4
    # resilience (--serve_watchdog_secs / --serve_preemption /
    # --serve_fault_inject; serving/resilience.py)
    watchdog_secs: float = 0.0      # 0 = no engine watchdog
    preemption: bool = True         # pool-pressure preemption
    fault_spec: str = ""            # chaos injection, e.g. "nan@12,hang@30"
    restart_backoff_secs: float = 0.5   # restart-storm backoff base
    # cache observatory (serving/cache_observatory.py): ghost-tier
    # capacity multiples for the digest-only shadow LRUs predicting the
    # prefix-cache hit rate at N x the pool ("cache" stats block,
    # cache_stats JSONL records)
    cache_ghost_multiples: Tuple[int, ...] = (2, 4, 10)
    # hierarchical KV cache (--serve_host_cache_bytes;
    # serving/host_cache.py): host-RAM budget for the spill tier under
    # the BlockManager.  0 disables the tier entirely (no thread, no
    # extra compiles).  Pages falling off the HBM LRU spill
    # asynchronously; admissions match digests against both tiers and
    # swap matched cold prefixes back with one fixed-shape host→device
    # scatter compiled at warmup.
    host_cache_bytes: int = 0


def _program(fn, name: str, owns: Tuple[int, ...] = ()):
    """``fn`` jitted under a stable name: the XLA module is
    ``jit_<name>`` in every profile and compile log, whatever the
    method behind it is called.  ``owns`` are the arguments the program
    is given for good (donated): it may write them in place, and the
    caller's arrays are deleted when the launch returns."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, donate_argnums=owns)


def _abstract(x) -> jax.ShapeDtypeStruct:
    """An argument as a compile saw it: shape, dtype and, of an array
    that was placed (committed), where it lies.  Lowering from these hits
    jit's own cache: the executable that ran, with no compile."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    placed = isinstance(x, jax.Array) and x.committed
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                sharding=x.sharding if placed else None)


def _beside(params):
    """Where the engine places the small arrays its programs read beside
    ``params``: replicated over the mesh (on the device) the weights were
    placed on, so that what the engine uploads and what a program gives
    back are arrays of one kind and a launch never meets a second one
    (which would be a second executable).  None, the default device and
    not committed, where the weights were never placed: a program's
    results are then not committed either."""
    for leaf in jax.tree_util.tree_leaves(params):
        if isinstance(leaf, jax.Array) and leaf.committed:
            where = leaf.sharding
            if isinstance(where, NamedSharding):
                return NamedSharding(where.mesh, PartitionSpec())
            if len(where.device_set) == 1:
                return SingleDeviceSharding(next(iter(where.device_set)))
            break
    return None


def _host_arrays(args) -> int:
    """The arrays among ``args`` (tables counted one each) that are the
    host's, which a launch uploads."""
    return sum(not isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(args))


# the per-slot sampling arrays of ``_EngineState`` that live on the device
# between launches, in the order the step programs take them
_SAMPLING = ("temps", "top_ks", "top_ps", "ban_a", "ban_b")


def _key_from_seed(seed: int) -> np.ndarray:
    # the two raw uint32 words of jax.random.PRNGKey(seed), built without
    # a device computation: PRNGKey(int) embeds the seed as a compile
    # constant, so calling it for a never-seen seed after warmup would
    # trigger a fresh compile and break the zero-recompile guarantee
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


@dataclass
class _EngineState:
    """Everything a restart replaces.  The wedged thread keeps its
    reference to the OLD state object, so whatever it writes when (if)
    it finally wakes up lands in abandoned arrays; request-visible
    effects are additionally gated on ``st is self._st`` after every
    dispatch.

    ``pages`` belongs to the engine's thread.  A decode step's launch
    CONSUMES the pool it is given (``_launch_step``: the arrays are
    deleted, ``pages`` is rebound to the program's output), so whoever
    reads ``pages`` off that thread does it under ``pool_lock``, which
    the launch holds from the call to the rebinding, and keeps no
    reference past the lock.

    What lives on the device between launches, and who may write it
    (the engine's thread alone, like every array here):

    * ``placed``: the device's copies of the sampling arrays
      (``_SAMPLING``) by name.  The host arrays stay the truth; whoever
      writes a slot's value there DROPS the name from ``placed``
      (``stale``), and the next launch uploads what is missing, once.
    * ``key_chain`` [S, 2]: every slot's PRNG key.  Only the decode and
      verify programs write it: a launch is handed the chain and gives
      back the next one, advanced for the slots that decoded.  The host
      never reads it.  Where the host itself sets a slot's key
      (``give_key``: an admission's seed, the key sampling a first token
      left) it writes its own ``keys`` and marks the row in
      ``keys_given``; the next launch uploads both and the program puts
      those rows into the chain before it splits.  ``keys[s]`` is
      therefore the key of a slot that has not decoded since it was
      given one, which is all ``_sample_first`` needs, and says nothing
      of a slot that has.
    * ``no_keys_given``: the ``(keys, keys_given)`` a launch is handed
      when the host gave no key since the last one: zeros, placed once."""

    gen: int
    blocks: BlockManager
    scheduler: Scheduler
    pages: Any
    last_tokens: np.ndarray
    context_lens: np.ndarray
    active: np.ndarray
    temps: np.ndarray
    top_ks: np.ndarray
    top_ps: np.ndarray
    ban_a: np.ndarray
    ban_b: np.ndarray
    keys: np.ndarray
    keys_given: np.ndarray
    key_chain: Any
    no_keys_given: Tuple[Any, Any]
    placed: Dict[str, Any] = field(default_factory=dict)
    pool_lock: threading.Lock = field(default_factory=threading.Lock)

    def stale(self, *names: str) -> None:
        """The host wrote a slot's value of the sampling arrays
        ``names`` (all of them if none is named): the device's copies
        are uploaded again before the next launch reads them."""
        for name in names or _SAMPLING:
            self.placed.pop(name, None)

    def give_key(self, slot: int, key) -> None:
        """The host sets ``slot``'s key: one row's update of the chain,
        made inside the next launch."""
        self.keys[slot] = key
        self.keys_given[slot] = True


class InferenceEngine:
    """Continuous-batching engine over one model + param set.

    ``submit()`` is thread-safe and returns a :class:`Request` future;
    the background thread (``start()``) moves requests through
    prefill -> decode -> completion.  Tokenization stays with the
    caller — the engine speaks token ids only."""

    # lint-enforced (graft-lint locks/LD002 + threads/TH001): the
    # state-object swap is the restart path's linearization point —
    # only restart() (under _restart_lock) may publish a new
    # _EngineState, and the thread/watchdog lifecycle fields share
    # that lock so stop() cannot race a watchdog-driven restart into
    # respawning a loop thread after shutdown.  finished is counted
    # from both the engine loop and restart (watchdog thread), so it
    # gets its own tiny lock.
    _lock_protected_ = {
        "_st": "_restart_lock",
        "_running": "_restart_lock",
        "_thread": "_restart_lock",
        "_watchdog": "_restart_lock",
        "finished": "_finished_lock",
    }

    def __init__(self, model, params, config: Optional[EngineConfig] = None):
        t_init = time.perf_counter()
        self.model = model
        self.params = params
        self.config = cfg = config or EngineConfig()
        mcfg = model.cfg
        if cfg.max_model_len <= 0:
            cfg.max_model_len = int(mcfg.max_position_embeddings)
        cfg.max_model_len = min(cfg.max_model_len,
                                int(mcfg.max_position_embeddings))
        self._max_blocks_per_slot = -(-cfg.max_model_len // cfg.block_size)
        self.queue = RequestQueue(cfg.max_queue_depth)

        # which path reads the pool is resolved ONCE, per program: it is
        # static data of the caches the programs hand the model, so
        # flipping it later would recompile.  'auto' takes the kernel
        # only where the programs run on ONE device, which is where this
        # engine's own arrays live, not how many chips the host happens
        # to have (a one-chip replica on a four-chip host keeps its
        # kernels).  The resolved value, not the requested mode, is what
        # /metrics and request_done report.
        one_device = len({d for leaf in jax.tree_util.tree_leaves(params)
                          if isinstance(leaf, jax.Array)
                          for d in leaf.devices()}) <= 1
        # where the per-slot arrays that stay on the device are placed
        self._beside = _beside(params)
        self.paged_kernel = paged_kv.resolve_kernel(cfg.paged_kernel,
                                                    one_device)
        self.prefill_kernel = paged_kv.resolve_kernel(cfg.prefill_kernel,
                                                      one_device)
        # a sparse model's expert blocks: static like the two above
        # (stats()['moe_expert_tiles'], left out for a dense model)
        self.moe_expert_tiles = grouped_matmul.moe_expert_tiles(mcfg)
        # the speculative [S, K+1] verify forward is another small-n
        # prefill call, so it rides the resolved PREFILL path.  draft_k
        # is a compiled shape: flipping it later would recompile, so it
        # is pinned here.
        if cfg.speculative and cfg.draft_k < 1:
            raise ValueError(f"speculative decoding needs draft_k >= 1, "
                             f"got {cfg.draft_k}")
        self.speculative = bool(cfg.speculative)
        self.draft_k = int(cfg.draft_k) if self.speculative else 0
        # what this model does not run with, of what this engine turns
        # on, is asked of the one table (config.RUNS_WITH); the prefix
        # cache is the table's one column that is turned off, not refused
        said = model_config.refusal(mcfg, [what for on, what in (
            (self.speculative, model_config.VERIFY_STEP),
            (cfg.int8_kv_cache, model_config.INT8_POOL),
            (cfg.host_cache_bytes > 0, model_config.HOST_TIER),
            (cfg.preemption, model_config.PREEMPTION)) if on])
        if said:
            raise ValueError(said)
        said = cfg.prefix_cache and model_config.refusal(
            mcfg, (model_config.PREFIX_CACHE,))
        if said:
            print(f" * {said}", flush=True)
            cfg.prefix_cache = False
        # everything this engine knows of its model's cache (the groups
        # of pools, a window group's sizes, the tables a program takes,
        # what a launch counts): ops/paged_kv.py's plan
        self._cache = paged_kv.plan(
            mcfg, cfg.block_size, cfg.num_slots, self._max_blocks_per_slot,
            cfg.prefill_chunk, self.prefill_kernel, self.paged_kernel,
            cfg.int8_kv_cache)
        # a model with no paged layer has no block to count: admission is
        # by slots (kv_blocks.BlockManager), and --serve_num_blocks,
        # which sizes a pool of pages, sizes nothing: the pool is the
        # garbage block alone
        if cfg.num_blocks and not self._cache.paged:
            raise ValueError(
                "num_blocks (--serve_num_blocks) sizes a pool of pages, and "
                "no layer of this model keeps pages: it is admitted by "
                "slots (--serve_num_slots)")
        self._num_blocks = 1 if not self._cache.paged else derive_num_blocks(
            cfg.num_slots, cfg.block_size, cfg.max_model_len,
            cfg.num_blocks or None)

        # cache observatory (serving/cache_observatory.py): per-prefix
        # heat, eviction forensics, ghost capacity tiers.  Engine-
        # lifetime like the loop profiler — restarts swap BlockManager
        # instances, the observatory keeps the accounting.
        self.cache_observatory = CacheObservatory(
            self._num_blocks - 1, cfg.block_size,
            ghost_multiples=cfg.cache_ghost_multiples)

        # host spill tier (serving/host_cache.py): constructed after the
        # first state so the per-block byte size is that of the actual
        # pools (dtype- and quantization-aware), then wired into the
        # manager + observatory.  Engine-lifetime like both.
        self.host_cache = None
        self._st = self._new_state(gen=0)
        if cfg.host_cache_bytes > 0 and cfg.prefix_cache:
            from megatron_llm_tpu.serving.host_cache import HostKVCache
            self.host_cache = HostKVCache(
                cfg.host_cache_bytes, paged_kv.block_bytes(self._st.pages),
                fetch=self._spill_fetch)
            self.cache_observatory.attach_host(self.host_cache)
            self._st.blocks.attach_host_cache(self.host_cache)
            self.host_cache.start()

        # the programs of a decode step own the pool (argument 1): the
        # step writes its rows in place, where a program that is only
        # lent the pool copies every array of it first.  The page
        # programs are lent theirs, and so is the chunk of a model with
        # pages, which somebody else may be reading (adoption,
        # copy-on-write, the host tier).  A pool with NO page has no such
        # reader: the chunk is given it for good and writes one slot's
        # state where it lies
        self._decode_step = _program(self._decode_impl, "engine_decode",
                                     owns=(1,))
        self._verify_step = _program(self._verify_impl, "engine_verify",
                                     owns=(1,))
        self._prefill_step = _program(
            self._prefill_impl, "engine_prefill",
            owns=() if self._cache.paged else (1,))
        self._sample_first = _program(self._sample_first_impl,
                                      "engine_sample_first")
        # the three page programs: copy-on-write, the host tier's
        # device→host spill source and its host→device swap-in.  src /
        # dst are traced int32 scalars and a host page has fixed shapes,
        # so one compile each (at warmup) covers every event
        # (over the pools that HAVE pages: a state-space layer's arrays
        # are indexed by slot and no page program sees them)
        self._cow_copy = _program(paged_kv.copy_page, "engine_cow_copy")
        self._fetch_block = _program(paged_kv.fetch_page,
                                     "engine_fetch_block")
        self._host_load = _program(paged_kv.load_page, "engine_host_load")
        # for program_tables(): the jitted programs themselves by the
        # names their XLA modules carry (the attributes may be wrapped),
        # and the tables once somebody has asked; the page programs only
        # where there are pages
        self._jitted = {
            "engine_decode": self._decode_step,
            "engine_verify": self._verify_step,
            "engine_prefill": self._prefill_step,
            "engine_sample_first": self._sample_first,
            **({"engine_cow_copy": self._cow_copy,
                "engine_fetch_block": self._fetch_block,
                "engine_host_load": self._host_load}
               if self._cache.paged else {})}
        self._program_tables: Optional[Dict[str, Any]] = None
        self.kv_pool_bytes = sum(
            a.nbytes for a in jax.tree_util.tree_leaves(self._st.pages))

        # counters (read by stats()/the HTTP /metrics endpoint)
        self.decode_steps = 0
        # of those, the steps whose sampler drew (a live row not greedy)
        # and sorted (such a row with an active top-k or top-p)
        self.sample_draw_steps = 0
        self.sample_sort_steps = 0
        self.prefill_chunks = 0
        self.tokens_generated = 0
        self.prefill_tokens_submitted = 0   # prompt tokens admitted
        self.prefill_tokens_computed = 0    # actually ran through prefill
        self.prefill_tokens_cached = 0      # adopted from the prefix cache
        # ... of which when a request's prefill began (_adopt_committed);
        # the rest at admission
        self.prefill_tokens_cached_at_prefill = 0
        self.occupancy_sum = 0          # sum of active slots over decode steps
        self.drafted_tokens = 0         # prompt-lookup proposals verified
        self.accepted_tokens = 0        # proposals committed by verify
        self.prefill_secs = 0.0
        self.decode_secs = 0.0
        self.finished: Dict[str, int] = {}
        self._finished_lock = threading.Lock()
        self.warmed_up = False
        # resilience counters + machinery (serving/resilience.py)
        self.engine_restarts = 0
        self.slots_evicted_nonfinite = 0
        self.fault_injector = ServingFaultInjector.from_spec(cfg.fault_spec)
        # the serve loop's span stream (serving/loop_profiler.py): one
        # record per launch with its phases' absolute times, surfaced as
        # the 'loop' block of stats(), periodic engine_loop_stats JSONL
        # records and loop.<phase> annotations on the profiler's clock.
        # Engine-lifetime (like the counters above): restarts swap the
        # state object, not the loop accounting.
        self.loop_profiler = LoopProfiler()
        self.loop_profiler.program_source = self.program_tables
        self._dispatches = 0            # prefill chunks + decode steps
        self._watchdog: Optional[EngineWatchdog] = None
        self._restart_lock = threading.Lock()
        self._restart_times: List[float] = []
        # called with every request_done record (ServerMetrics feeds its
        # SLO histograms from here); exceptions never reach the engine loop
        self.request_done_hook: Optional[Any] = None

        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self._submit_lock = threading.Lock()
        # the pools, the tables and the placed slot state, on the
        # start-up timeline
        tracing.startup_completed("engine_init", t_init,
                                  time.perf_counter())

    def _new_state(self, gen: int,
                   carry: Optional[_EngineState] = None) -> _EngineState:
        """Fresh restartable state.  Shapes are identical every time, so
        the page init and every jitted program cache-hit — a restart
        compiles nothing.  Scheduler counters carry across restarts (the
        fleet-visible totals must not reset)."""
        cfg = self.config
        if carry is not None:
            # the fresh pool starts empty: ghost slots release their
            # blocks but digest residency survives the restart
            self.cache_observatory.on_pool_reset()
            if self.host_cache is not None:
                # queued spills reference the abandoned pool; resident
                # host entries and counters survive the restart
                self.host_cache.on_pool_reset()
        pages = self._cache.init_pools(self._num_blocks)
        blocks = BlockManager(
            self._num_blocks, cfg.block_size, cfg.num_slots,
            self._max_blocks_per_slot, prefix_cache=cfg.prefix_cache,
            observatory=self.cache_observatory, host_cache=self.host_cache,
            window=self._cache.window and WindowGroup(*self._cache.window),
            state_bytes_per_slot=self._cache.state_bytes_per_slot,
            paged=self._cache.paged)
        sched = Scheduler(self.queue, blocks, cfg.max_model_len,
                          draft_k=self.draft_k)
        if carry is not None:
            old = carry.scheduler
            sched.admitted = old.admitted
            sched.rejected_len = old.rejected_len
            sched.deadline_evictions = old.deadline_evictions
            sched.preemptions = old.preemptions
            sched.swap_in_blocks_reserved = old.swap_in_blocks_reserved
            # prefix-cache counters carry too: the observatory's shadow
            # counters are cumulative across restarts (it is shared, see
            # on_pool_reset above), and check_invariants asserts the
            # manager's totals equal them
            ob = carry.blocks
            blocks.prefix_cache_hits = ob.prefix_cache_hits
            blocks.prefix_cache_misses = ob.prefix_cache_misses
            blocks.prefix_cache_evictions = ob.prefix_cache_evictions
            blocks.prefix_cache_hit_tokens = ob.prefix_cache_hit_tokens
            blocks.prefix_cache_host_hits = ob.prefix_cache_host_hits
            blocks.cow_copies = ob.cow_copies
        S = cfg.num_slots
        keys, given = np.zeros((S, 2), np.uint32), np.zeros(S, bool)
        return _EngineState(
            gen=gen,
            blocks=blocks,
            scheduler=sched,
            pages=pages,
            last_tokens=np.zeros(S, np.int32),
            context_lens=np.zeros(S, np.int32),
            active=np.zeros(S, np.int32),
            temps=np.ones(S, np.float32),
            top_ks=np.zeros(S, np.int32),
            top_ps=np.zeros(S, np.float32),
            ban_a=np.full(S, -1, np.int32),
            ban_b=np.full(S, -1, np.int32),
            keys=keys,
            keys_given=given,
            key_chain=self._place(keys),
            no_keys_given=(self._place(keys), self._place(given)),
        )

    def _place(self, host: np.ndarray):
        """A copy of the host's array on the device, where the programs'
        own results lie (``_beside``).  Of a copy: a CPU device may keep
        the buffer it is handed, and the host writes its arrays on."""
        return jax.device_put(host.copy(), self._beside)

    def _resident(self, st: _EngineState, d: DispatchRecord) -> tuple:
        """What a decode or verify launch takes after the arrays that
        change every step, none of it the host's: the sampling arrays
        (those a write made stale uploaded first), the key chain, and
        the keys the host gave since the last launch with the rows they
        are for.  Uploads are counted on ``d``."""
        for name in _SAMPLING:
            if name not in st.placed:
                st.placed[name] = self._place(getattr(st, name))
                d.host_uploads += 1
        given = st.no_keys_given
        if st.keys_given.any():
            given = self._place(st.keys), self._place(st.keys_given)
            st.keys_given[:] = False
            d.host_uploads += 2
        return (*(st.placed[name] for name in _SAMPLING), st.key_chain,
                *given)

    @property
    def _layer_groups(self) -> Optional[tuple]:
        """The pool group of each layer (the plan's): what
        ``paged_kv.step_caches`` takes beside the pools."""
        return self._cache.groups

    # current-state views (the HTTP server, tools and tests address the
    # engine, not a state generation)
    @property
    def blocks(self) -> BlockManager:
        return self._st.blocks

    @property
    def scheduler(self) -> Scheduler:
        return self._st.scheduler

    # ------------------------------------------------------------------
    # jitted device programs (fixed shapes; everything traced)
    # ------------------------------------------------------------------

    @staticmethod
    def _split_keys(keys, given, keys_given, live):
        """One step of the key chain ``keys`` [S, 2]: the rows the host
        gave since the last launch (``keys_given``) take ``given``'s key
        first; every slot's key is split once, and the chain advances
        ONLY for a slot that decodes this step (``live``): a slot
        mid-prefill keeps its admission-time seed key, so a request's
        sample stream depends on its seed alone, not on batch-mates'
        decode traffic.  Returns the keys to sample with and the chain
        as the next launch takes it."""
        keys = jnp.where(keys_given[:, None], given, keys)
        sub = jax.vmap(lambda k: jax.random.split(k, 2))(keys)  # [S, 2, 2]
        return sub[:, 0], jnp.where(live[:, None], sub[:, 1], keys)

    def _decode_impl(self, params, pages, last_tokens, context_lens,
                     block_tables, active, temps, top_ks, top_ps,
                     ban_a, ban_b, keys, given_keys, keys_given):
        tokens = last_tokens[:, None]                       # [S, 1]
        positions = context_lens[:, None]                   # [S, 1]
        caches = paged_kv.step_caches(pages, block_tables, context_lens,
                                      active, self.paged_kernel,
                                      self._layer_groups)
        logits, new_caches = language_model_forward(
            params, tokens, positions, None, self.model.cfg,
            rng_key=None, train=False, kv_caches=caches)
        logits = logits[:, 0, :].astype(jnp.float32)        # [S, V]
        # non-finite sentinel: per-slot health of the raw model logits,
        # computed before the (legitimately -inf) ban masking below.
        # Rides this same program and is fetched with the tokens — the
        # host-side check costs no dispatch and no recompile.
        finite = jnp.isfinite(logits).all(axis=-1)          # [S] bool
        V = logits.shape[-1]
        # ban pair (prevent_newline_after_colon): token b is illegal
        # immediately after token a
        banned = (ban_a >= 0) & (last_tokens == ban_a)
        hit = jnp.arange(V)[None, :] == jnp.clip(ban_b, 0, V - 1)[:, None]
        logits = jnp.where(banned[:, None] & hit, NEG_INF, logits)
        draw, chain = self._split_keys(keys, given_keys, keys_given,
                                       active > 0)
        with jax.named_scope("sampler"):
            next_tokens = sample_batched(logits, draw, top_ks, top_ps,
                                         temps, active > 0)
        return (next_tokens, paged_kv.pools_of(new_caches), chain,
                finite, paged_kv.routing_of(new_caches))

    def _verify_impl(self, params, pages, tokens, context_lens,
                     block_tables, vlens, temps, top_ks, top_ps,
                     ban_a, ban_b, keys, given_keys, keys_given):
        """Speculative [S, K+1] verify step — the decode program when
        ``speculative`` is on.  Row s carries ``[last_token, draft_1..
        draft_L, pad]`` with ``vlens[s] = 1 + L`` (0 for inactive
        slots); the paged scatter-before-read branch writes the valid
        prefix's KV at ``context_lens[s]..`` and redirects padded and
        inactive rows to the garbage block, exactly like a prefill
        chunk.  Output row 0 goes through ``sample_batched`` with ONE
        key split per slot — a non-drafting (sampled or draft-less)
        slot therefore sees bit-identical logits, key chain and token
        stream to the plain decode program.  Rows >= 1 are raw argmax:
        only exact-greedy slots draft, and argmax of row j is exact
        whenever drafts 1..j all matched (the host accept rule commits
        no further)."""
        K1 = tokens.shape[1]
        positions = context_lens[:, None] + jnp.arange(K1)[None, :]
        caches = paged_kv.step_caches(pages, block_tables, context_lens,
                                      vlens, self.prefill_kernel,
                                      self._layer_groups)
        logits, new_caches = language_model_forward(
            params, tokens, positions, None, self.model.cfg,
            rng_key=None, train=False, kv_caches=caches)
        logits = logits.astype(jnp.float32)             # [S, K+1, V]
        # per-slot sentinel over the VALID rows only — padded rows
        # attend garbage KV and may legitimately misbehave
        row_valid = jnp.arange(K1)[None, :] < vlens[:, None]
        finite = (jnp.isfinite(logits).all(axis=-1)
                  | ~row_valid).all(axis=-1)            # [S] bool
        V = logits.shape[-1]
        # ban pair per position: row j samples the token following
        # tokens[:, j], so that input token is the "previous" one
        banned = (ban_a[:, None] >= 0) & (tokens == ban_a[:, None])
        hit = (jnp.arange(V)[None, None, :]
               == jnp.clip(ban_b, 0, V - 1)[:, None, None])
        logits = jnp.where(banned[:, :, None] & hit, NEG_INF, logits)
        draw, chain = self._split_keys(keys, given_keys, keys_given,
                                       vlens > 0)
        with jax.named_scope("sampler"):
            first = sample_batched(logits[:, 0, :], draw, top_ks,
                                   top_ps, temps, vlens > 0)
        emit = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        emit = emit.at[:, 0].set(first.astype(jnp.int32))
        return (emit, paged_kv.pools_of(new_caches), chain, finite,
                paged_kv.routing_of(new_caches))

    def _prefill_impl(self, params, pages, tokens, start_pos, valid_len,
                      block_table):
        C = tokens.shape[1]
        positions = (start_pos + jnp.arange(C))[None, :]    # [1, C]
        cfg = self.model.cfg
        block_table, last = self._cache.chunk_given(block_table)
        caches = paged_kv.step_caches(
            pages, block_table, jnp.full((1,), start_pos, jnp.int32),
            jnp.full((1,), valid_len, jnp.int32), self.prefill_kernel,
            self._layer_groups)
        h, new_caches = language_model_forward(
            params, tokens, positions, None, cfg, rng_key=None,
            train=False, kv_caches=caches, compute_logits=False)
        # the head once a request and for one row: only the chunk that
        # ends its context has its logits read, and of them the row of
        # its last live token; every other chunk gives zeros
        row = jax.lax.dynamic_slice_in_dim(h, valid_len - 1, 1, axis=1)
        logits = jax.lax.cond(
            last,
            lambda: lm_head_logits(params, row, cfg)[0, 0].astype(
                jnp.float32),
            lambda: jnp.zeros((cfg.padded_vocab_size,), jnp.float32))
        return (logits, paged_kv.pools_of(new_caches),
                paged_kv.routing_of(new_caches))

    def _spill_fetch(self, manager, block: int):
        """host_cache spill-thread callback: device→host copy of one
        page.  Runs on the spill thread; the abandoned-manager guard
        keeps a post-restart queue drain from reading the fresh pool
        through a stale block id.  A decode step consumes the pool it
        is given, so the read is SERIALISED with the launches: the
        gather is dispatched from the pool as it stands under
        ``pool_lock`` (which a launch holds until ``st.pages`` is the
        pool that replaced it), and what it returns is an array of its
        own, fetched with the lock released.  Which pool is read does
        not matter: the spill tier only fetches digest-registered
        pages, whose content is frozen (COW and eviction both
        unregister first), and the caller re-validates the (block,
        epoch) mapping after this returns."""
        st = self._st
        if st.blocks is not manager:
            return None
        # a state whose thread is wedged inside a launch never gives the
        # lock back: give up on it once a restart has replaced it
        while not st.pool_lock.acquire(timeout=0.05):
            if st is not self._st:
                return None
        try:
            page = self._fetch_block(st.pages, np.int32(block))
        finally:
            st.pool_lock.release()
        return jax.device_get(page)

    def _sample_first_impl(self, logits, key, top_k, top_p, temp,
                           ban_a, ban_b, last_prompt_tok):
        finite = jnp.isfinite(logits).all()     # sentinel, pre-masking
        logits = logits[None, :]                            # [1, V]
        V = logits.shape[-1]
        banned = (ban_a >= 0) & (last_prompt_tok == ban_a)
        hit = jnp.arange(V)[None, :] == jnp.clip(ban_b, 0, V - 1)
        logits = jnp.where(banned & hit, NEG_INF, logits)
        sub = jax.random.split(key, 2)
        with jax.named_scope("sampler"):
            tok = sample_batched(logits, sub[0][None], top_k[None],
                                 top_p[None], temp[None], jnp.ones(1, bool))
        return tok[0], sub[1], finite

    # ------------------------------------------------------------------
    # submission (any thread)
    # ------------------------------------------------------------------

    def submit(self, prompt_tokens: Sequence[int],
               sampling: Optional[SamplingParams] = None,
               stream: bool = False,
               deadline_secs: Optional[float] = None,
               trace_id: Optional[str] = None) -> Request:
        return self.submit_many([list(prompt_tokens)],
                                [sampling or SamplingParams()],
                                stream=stream,
                                deadline_secs=deadline_secs,
                                trace_id=trace_id)[0]

    def submit_many(self, prompts: Sequence[Sequence[int]],
                    samplings: Sequence[Optional[SamplingParams]],
                    stream: bool = False,
                    deadline_secs: Optional[float] = None,
                    trace_id: Optional[str] = None) -> List[Request]:
        """Atomic multi-request admission: validates and enqueues all, or
        raises (ValueError -> HTTP 400, QueueFull -> HTTP 429) enqueueing
        none.  ``trace_id`` (the router's X-Request-Trace) is shared by
        every sub-request of a multi-prompt call — they are one client
        request."""
        if deadline_secs is None:
            deadline_secs = (self.config.default_deadline_secs or None)
        reqs = []
        for toks, sp in zip(prompts, samplings):
            r = Request(toks, sp or SamplingParams(), stream=stream,
                        deadline_secs=deadline_secs, trace_id=trace_id)
            r._pc_submit = time.perf_counter()
            self.scheduler.validate(r)
            reqs.append(r)
        with self._submit_lock:
            self.queue.put_many(reqs)   # raises QueueFull atomically
        self._wake.set()
        return reqs

    # ------------------------------------------------------------------
    # engine thread
    # ------------------------------------------------------------------

    def start(self) -> "InferenceEngine":
        with self._restart_lock:
            assert self._thread is None, "engine already started"
            # "ready": the start-up timeline closes here and prints its
            # line (nothing where no span was opened since the last)
            tracing.startup_ready()
            self._running = True
            if self.config.watchdog_secs > 0 and self._watchdog is None:
                self._watchdog = EngineWatchdog(
                    timeout_secs=self.config.watchdog_secs,
                    has_work=lambda: self._st.scheduler.has_work(),
                    on_fire=lambda: self.restart("watchdog")).start()
            self._thread = threading.Thread(target=self._loop,
                                            name="serving-engine",
                                            daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        # Lifecycle writes happen under _restart_lock so a concurrent
        # watchdog restart() either completes first (we then join the
        # thread it spawned) or observes _running False and stands
        # down — it can never respawn the loop after shutdown.
        with self._restart_lock:
            self._running = False
            watchdog, self._watchdog = self._watchdog, None
            thread, self._thread = self._thread, None
            self._wake.set()
        # join OUTSIDE the lock: the watchdog's on_fire path takes
        # _restart_lock, so joining it while holding the lock is the
        # classic drain/watchdog deadlock (threads/TH003 shape)
        if watchdog is not None:
            watchdog.stop()
        if thread is not None:
            thread.join(timeout)
        st = self._st
        for req in self.queue.drain():
            req._finish(FINISH_ABORTED)
        for req in list(st.scheduler.active.values()):
            req._finish(FINISH_ABORTED)
            st.scheduler.evict(req)
        # stop the spill thread before the final flushes so the host
        # block of the flushed cache_stats is its terminal state
        if self.host_cache is not None:
            self.host_cache.close()
        # final loop-goodput + cache-observatory flush BEFORE
        # engine_stop, so the last engine_loop_stats / cache_stats
        # records and stats() agree exactly (no dispatches or
        # admissions can land in between)
        self.loop_profiler.maybe_emit(force=True)
        self.cache_observatory.maybe_emit(force=True)
        stream = telemetry.get_stream()
        if stream is not None:
            stream.emit({"kind": "serve", "event": "engine_stop",
                         **self.stats()})

    def _loop(self) -> None:
        st = self._st
        while self._running and st is self._st:
            try:
                did_work = self.step(st)
            except Exception as e:  # noqa: BLE001 - engine must survive
                msg = f"{type(e).__name__}: {e}"
                if self._pool_consumed(st):
                    # the launch that raised had taken its pool: this
                    # state can launch nothing more, so it goes the way
                    # of a wedged one (a fresh pool, requests requeued)
                    if st is self._st:
                        self.restart("a launch raised holding the pool: "
                                     + msg)
                    return
                self._fail_all(st, msg)
                did_work = False
            if st is not self._st:
                return              # restarted under our feet: stand down
            if did_work and self._watchdog is not None:
                self._watchdog.progress()
            if not did_work:
                self._wake.wait(timeout=0.05)
                self._wake.clear()

    @staticmethod
    def _pool_consumed(st: _EngineState) -> bool:
        """Whether a decode step's launch took ``st.pages`` and raised
        before it gave a pool back."""
        return any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(st.pages))

    def _fail_all(self, st: _EngineState, msg: str) -> None:
        st.active[:] = 0
        for req in list(st.scheduler.active.values()):
            req._finish(FINISH_ERROR, error=msg)
            st.scheduler.evict(req)
            self._count_finish(FINISH_ERROR)

    def restart(self, reason: str) -> None:
        """Tear down and restart the engine in-process: swap in a fresh
        state (identical shapes — every jitted program cache-hits),
        requeue interrupted requests that never produced a byte at the
        queue head, fail mid-stream ones cleanly, and replace the engine
        thread.  The wedged thread keeps the abandoned state object and
        is gated out of every request-visible effect.  Callable from any
        thread (the watchdog calls it from its own)."""
        with self._restart_lock:
            old = self._st
            self.engine_restarts += 1
            requeue: List[Request] = []
            failed: List[Request] = []
            for req in list(old.scheduler.active.values()):
                if req.state == RequestState.DONE:
                    continue
                if req._events is not None and req.t_first_token is not None:
                    failed.append(req)      # streamed bytes already left
                else:
                    requeue.append(req)
            tracing.instant("engine_restart", "serve", reason=reason,
                            gen=old.gen, requeued=len(requeue),
                            failed=len(failed))
            stream = telemetry.get_stream()
            if stream is not None:
                stream.emit({"kind": "serve", "event": "engine_restart",
                             "reason": reason, "gen": old.gen,
                             "requeued": len(requeue),
                             "failed": len(failed)})
            # publish the fresh state FIRST: from here on the old thread
            # fails its `st is self._st` guards and cannot touch requests
            self._st = self._new_state(gen=old.gen + 1, carry=old)
            for req in failed:
                req._finish(FINISH_ERROR,
                            error=f"engine restarted mid-stream ({reason})")
                self._count_finish(FINISH_ERROR)
            # queue-head requeue in original submit order (last submitted
            # inserted first ends up behind earlier ones)
            for req in sorted(requeue, key=lambda r: r.t_submit,
                              reverse=True):
                req.reset_for_requeue()
                self.queue.put_front(req)
            # restart-storm backoff: repeated fires within a minute back
            # off exponentially so a hard-wedged model can't hot-loop
            # dump/restart cycles
            now = time.monotonic()
            self._restart_times = [t for t in self._restart_times
                                   if now - t < 60.0] + [now]
            storms = len(self._restart_times) - 1
            if storms > 0 and self.config.restart_backoff_secs > 0:
                delay = min(self.config.restart_backoff_secs
                            * 2 ** (storms - 1), 30.0)
                print(f" [engine] restart storm ({storms + 1} in 60s): "
                      f"backing off {delay:.1f}s", flush=True)
                time.sleep(delay)
            if self._running:
                self._thread = threading.Thread(
                    target=self._loop, name="serving-engine", daemon=True)
                self._thread.start()
            if self._watchdog is not None:
                self._watchdog.progress()
            self._wake.set()

    def step(self, st: Optional[_EngineState] = None) -> bool:
        """One scheduling decision + device call.  Returns False when
        idle.  Public so tests can single-step the engine without the
        background thread."""
        st = st if st is not None else self._st
        sched = st.scheduler
        # loop goodput: everything from here to the _run_* handoff is
        # the 'schedule' phase (deadline sweep, admission, preemption,
        # slot bookkeeping, the scheduling decision itself)
        d = self.loop_profiler.begin()
        # fault injection stays disarmed through warmup — chaos specs
        # index steady-state dispatches
        inj = self.fault_injector if self.warmed_up else None
        for req in sched.sweep_deadlines():
            req._finish(FINISH_DEADLINE)
            self._retire(st, req)
        t_admit = time.perf_counter()
        admitted = []
        if inj is not None and inj.maybe_oom(self._dispatches + 1):
            pass        # injected pool exhaustion: head retries next step
        else:
            for req in sched.admit():
                self._on_admit(st, req)
                admitted.append(req)
            if not admitted and self.config.preemption:
                admitted = self._try_preempt(st)
        if admitted:
            # slot-setup cost, split evenly across this round's admits
            share = (time.perf_counter() - t_admit) / len(admitted)
            for req in admitted:
                req.admission_secs += share
        # periodic cache_stats JSONL (cadence logic keeps this a no-op
        # almost always; a None stream returns before any lock)
        self.cache_observatory.maybe_emit()
        kind, arg = sched.next_action()
        if kind == "prefill":
            self._dispatches += 1
            if inj is not None:
                inj.before_dispatch(self._dispatches)
            d.kind = "prefill"
            d.mark("schedule")
            self._run_prefill_chunk(st, arg, d)
            return True
        if kind == "decode":
            self._dispatches += 1
            if inj is not None:
                inj.before_dispatch(self._dispatches)
            # one decode path: with speculation on EVERY decode step is
            # the [S, K+1] verify program — draft-less and sampled slots
            # ride it masked (vlen = 1), so the plain decode program is
            # never dispatched and cannot cause a late first compile
            d.kind = "verify" if self.speculative else "decode"
            d.mark("schedule")
            if self.speculative:
                self._run_verify(st, arg, d)
            else:
                self._run_decode(st, arg, d)
            return True
        # no action: not a launch, and the wait for new work must not
        # read as a dispatch gap
        self.loop_profiler.idle(d)
        return False

    # -- admission ------------------------------------------------------

    def _on_admit(self, st: _EngineState, req: Request) -> None:
        s = req.slot
        sp = req.sampling
        st.temps[s] = sp.temperature
        st.top_ks[s] = sp.top_k
        st.top_ps[s] = sp.top_p
        st.ban_a[s] = sp.ban_pair[0] if sp.ban_pair else -1
        st.ban_b[s] = sp.ban_pair[1] if sp.ban_pair else -1
        st.stale()
        st.give_key(s, _key_from_seed(sp.seed))
        st.active[s] = 0            # stays masked until prefill done
        st.context_lens[s] = 0
        self.prefill_tokens_submitted += len(req.prompt_tokens)
        self.prefill_tokens_cached += req.cached_prompt_tokens
        req._pc_admit = time.perf_counter()
        req.queue_wait_secs = req._pc_admit - req._pc_submit
        tracer = tracing.get_tracer()
        if tracer is not None:
            # queue wait as a span: visible dead-time on the timeline
            # between the client's submit and the slot grant
            tracer.completed("queue_wait", "serve", req._pc_submit,
                             req.queue_wait_secs, request=req.id,
                             trace=req.trace_id)
        tracing.instant("admit", "serve", request=req.id, slot=s,
                        trace=req.trace_id,
                        prompt_tokens=len(req.prompt_tokens),
                        cached_prompt_tokens=req.cached_prompt_tokens)
        if req.cached_prompt_tokens > 0:
            tracing.instant("prefix_cache_hit", "serve", request=req.id,
                            trace=req.trace_id, at="admission",
                            tokens=req.cached_prompt_tokens)

    # -- pool-pressure preemption ---------------------------------------

    def _try_preempt(self, st: _EngineState) -> List[Request]:
        """Admission stalled with work queued: when a slot is free but
        the head's worst-case block reservation is not (a deliberately
        oversubscribed pool), evict a strictly-larger running request
        and retry.  Returns the requests admitted into the freed
        capacity (empty when preemption cannot help)."""
        head = self.queue.peek()
        if head is None or head.past_deadline():
            return []
        bstats = st.blocks.stats()
        if bstats["slots_in_use"] >= bstats["slots_total"]:
            return []       # slot-bound, not block-bound: just wait
        victim = st.scheduler.select_victim(head)
        if victim is None:
            return []
        # requeue order matters: preempt() put_fronts the victim, which
        # would place it AHEAD of the head it is yielding to — FIFO
        # admission would then hand the victim straight back its own
        # freed pages.  Pop the head first and re-front it after, so the
        # queue reads [head, victim, ...] and the freed capacity goes to
        # the smaller request (the engine thread is the only popper, so
        # the pop/put_front pair cannot lose a request).
        popped = self.queue.pop()
        self._preempt(st, victim)
        if popped is not None:
            self.queue.put_front(popped)
        admitted = []
        for req in st.scheduler.admit():
            self._on_admit(st, req)
            admitted.append(req)
        return admitted

    def _preempt(self, st: _EngineState, victim: Request) -> None:
        s = victim.slot
        # tokens with KV actually on device (see _retire): registered so
        # the victim's re-admission re-adopts its own pages
        n_written = (int(st.context_lens[s]) if st.context_lens[s] > 0
                     else victim.prefill_pos)
        st.active[s] = 0
        st.context_lens[s] = 0
        tracing.instant("preempt", "serve", request=victim.id, slot=s,
                        trace=victim.trace_id,
                        generated=len(victim.out_tokens))
        stream = telemetry.get_stream()
        if stream is not None:
            stream.emit({"kind": "serve", "event": "preemption",
                         "request": victim.id, "trace_id": victim.trace_id,
                         "generated": len(victim.out_tokens),
                         "n_written": n_written})
        st.scheduler.preempt(victim, token_ids=victim.chain,
                             n_written=n_written)

    # -- prefill --------------------------------------------------------

    def _window_advance(self, st: _EngineState, d: DispatchRecord,
                        writes) -> None:
        """Before a launch of a model with a window group: for each
        (slot, start, n) of ``writes`` take the window pages the launch
        writes and give back those behind its window
        (``WindowGroup.advance_locked``); then what the pool holds against the
        tokens it holds them for, on the record."""
        if st.blocks.window is None:
            return
        with TraceAnnotation("loop.kv_release", seq=d.seq):
            (d.kv_window_pages_returned, d.kv_window_pages_spanned,
             d.kv_full_pages_held, held) = st.blocks.window_advance(writes)
            full_bytes, window_bytes = self._cache.group_block_bytes
            d.kv_held_bytes = (d.kv_full_pages_held * full_bytes
                               + held * window_bytes)
            d.kv_live_tokens = sum(
                max(int(st.context_lens[r.slot]), r.prefill_pos)
                for r in st.scheduler.active.values())

    def _writable(self, st: _EngineState, slot: int, block_idx: int) -> None:
        """Copy-on-write barrier before a device write into a slot's
        logical page: if the block manager swaps in a private copy,
        mirror the page contents on device.  A model with no page has
        none to make writable."""
        if not self._cache.paged:
            return
        res = st.blocks.ensure_writable(slot, block_idx)
        if res is not None:
            new_b, src_b = res
            st.pages = self._copy_page(st.pages, src_b, new_b)

    def _copy_page(self, pages, src: int, dst: int):
        """``pages`` with page ``src`` of every pool that has pages
        copied to ``dst``."""
        copied = self._cow_copy(paged_kv.paged_pools(pages), np.int32(src),
                                np.int32(dst))
        return paged_kv.with_paged(pages, copied)

    def _swap_in(self, st: _EngineState, req: Request) -> None:
        """Replay the slot's host-tier hits: one fixed-shape
        host→device scatter per pending block, before the first prefill
        chunk touches the slot.  A missing host entry (only possible
        across an engine restart, which clears pins) truncates the
        cached prefix at the first gap — the tail recomputes through
        the normal prefill path instead."""
        pending = st.blocks.take_pending_swap_ins(req.slot)
        if not pending:
            return
        t0 = time.perf_counter()
        host = self.host_cache
        loaded: List[Tuple[int, bytes]] = []
        valid_blocks: Optional[int] = None
        for i, (block_idx, block, digest) in enumerate(pending):
            data = host.take_for_swap_in(digest)
            if data is None:
                valid_blocks = block_idx
                host.unpin([dg for _, _, dg in pending[i + 1:]])
                break
            st.pages = self._host_load(st.pages, data, np.int32(block))
            loaded.append((block, digest))
        jax.block_until_ready(st.pages[self._cache.first_pool])
        secs = time.perf_counter() - t0
        if valid_blocks is not None:
            cached = valid_blocks * self.config.block_size
            lost = max(req.cached_prompt_tokens - cached, 0)
            req.prefill_pos = min(req.prefill_pos, cached)
            req.cached_prompt_tokens = cached
            self.prefill_tokens_cached -= lost
        st.blocks.complete_swap_ins(req.slot, loaded)
        req.swap_in_secs += secs
        req.host_hit_blocks = len(loaded)
        host.note_swap_in(len(loaded), secs)
        tracing.instant("host_swap_in", "serve", request=req.id,
                        trace=req.trace_id, blocks=len(loaded),
                        secs=round(secs, 6))

    def _adopt_committed(self, st: _EngineState, req: Request) -> None:
        """The prefix cache asked again, before every chunk: admission
        matched ``req`` against what the cache held then, and requests
        admitted beside it (a document's other questions, a system
        prompt's other users) have committed pages since.  What the
        cache holds NOW from ``req.prefill_pos`` on is adopted
        (``BlockManager.adopt_committed``) and prefill goes on behind
        it.  A miss is one dictionary lookup; a model that adopts no
        prefix returns at once.  The block tables are read off the host
        at every launch (``CachePlan.tables``), so no copy of them on
        the device goes stale."""
        adopted = st.blocks.adopt_committed(req.slot, req.chain,
                                            req.prefill_pos)
        if not adopted:
            return
        req.prefill_pos += adopted
        req.cached_prompt_tokens += adopted
        self.prefill_tokens_cached += adopted
        self.prefill_tokens_cached_at_prefill += adopted
        tracing.instant("prefix_cache_hit", "serve", request=req.id,
                        trace=req.trace_id, at="prefill", tokens=adopted)

    def _run_prefill_chunk(self, st: _EngineState, req: Request,
                           d: DispatchRecord) -> None:
        if self.host_cache is not None:
            # consume pending host-tier swap-ins first (no-op after the
            # slot's first chunk); accounted to the build_inputs bucket
            self._swap_in(st, req)
        self._adopt_committed(st, req)
        C = self.config.prefill_chunk
        # prefill over the full context — prompt plus anything generated
        # before a preemption/restart requeued this request (identical to
        # the prompt for never-interrupted requests)
        ptoks = req.context_tokens()
        start = req.prefill_pos
        chunk = ptoks[start:start + C]
        valid = len(chunk)
        toks = np.zeros((1, C), np.int32)
        toks[0, :valid] = chunk
        bs = self.config.block_size
        for bi in range(start // bs, (start + valid - 1) // bs + 1):
            self._writable(st, req.slot, bi)
        self._window_advance(st, d, [(req.slot, start, valid)])
        # whether this chunk ends the context: the one that runs the head
        done = start + valid >= len(ptoks)
        table = self._cache.chunk_tables(st.blocks, req.slot, done)
        d.start, d.valid = start, valid
        d.prefill_head_rows = int(done)
        d.cached_tokens = req.cached_prompt_tokens
        d.requests = (req.id,)
        d.traces = (req.trace_id,) if req.trace_id else ()
        self._cache.account(d, np.asarray([start]), np.asarray([valid]), C,
                            len(st.scheduler.active))
        d.mark("build_inputs")
        finite = True
        handed = (toks, np.int32(start), np.int32(valid), table)
        d.host_uploads += _host_arrays(handed)
        # under the lock a decode step's launch holds: where the chunk
        # owns its pool (no paged layer) it consumes it as that step does
        with st.pool_lock:
            last_logits, st.pages, routing = self._prefill_step(
                self.params, st.pages, *handed)
        if done:
            # the slot has not decoded since the host gave it its key, so
            # the host's row IS its key (_EngineState)
            first = (st.keys[req.slot],
                     st.top_ks[req.slot], st.top_ps[req.slot],
                     st.temps[req.slot], st.ban_a[req.slot],
                     st.ban_b[req.slot],
                     np.int32(ptoks[-1]))
            d.host_uploads += _host_arrays(first)
            tok, new_key, finite = self._sample_first(last_logits, *first)
            d.mark("dispatch")
            tok, new_key, finite, routing = self._read(
                d, tok, new_key, finite, routing)
            tok = int(tok)
            finite = bool(finite)
            st.give_key(req.slot, new_key)
        else:
            d.mark("dispatch")
            # a chunk that is not its prompt's last: nothing of it is
            # read but the histogram, and the wait is for the chunk
            (routing,) = self._read(
                d, routing, until=st.pages[self._cache.first_pool])
        d.mark("fetch")
        self._cache.account_routing(d, routing)
        if st is not self._st:
            self.loop_profiler.finish(d)
            return          # engine restarted mid-dispatch: stale state
        chunk_secs = d.wait_secs
        self.prefill_secs += chunk_secs
        req.prefill_compute_secs += chunk_secs
        self.prefill_chunks += 1
        self.prefill_tokens_computed += valid
        req.prefill_pos = start + valid
        # freshly filled full blocks become shareable right away, so a
        # burst of same-prefix requests hits even mid-prefill
        st.blocks.commit_prefix(req.slot, req.chain, req.prefill_pos)
        if not done:
            self.loop_profiler.finish(d)
            return
        inj = self.fault_injector if self.warmed_up else None
        if inj is not None and inj.poison_nonfinite(self._dispatches):
            finite = False
        if not finite:
            self._evict_nonfinite(st, req)
            self.loop_profiler.finish(d)
            return
        # prompt fully cached: request enters the decode batch
        s = req.slot
        req.state = RequestState.DECODE
        st.context_lens[s] = len(ptoks)
        st.active[s] = 1
        st.last_tokens[s] = tok
        self._emit_and_check(st, req, tok)
        self.loop_profiler.finish(d)

    # -- decode ---------------------------------------------------------

    @staticmethod
    def _read(d: DispatchRecord, *results, until=None) -> tuple:
        """A launch's results on the host (None stays None), one wait:
        every one of them sets out for the host as the launch returns,
        so none starts its trip only when the one before it is home.
        ``until``: an array the launch gives that nobody reads, waited
        for all the same."""
        d.host_reads += 1
        home = jax.device_get(results)
        if until is not None:
            jax.block_until_ready(until)
        return home

    def _note_batch(self, st: _EngineState, d: DispatchRecord,
                    slots: List[int], decoding: List[Request]) -> None:
        """What a decode/verify launch works on, for its record.  The
        sampler's rows are a record of what ``sample_batched`` decides
        from the same arrays on the device, never its input."""
        d.rows = len(slots)
        d.context_tokens = int(st.context_lens[slots].sum())
        d.requests = tuple(r.id for r in decoding)
        d.traces = tuple(sorted({r.trace_id for r in decoding
                                 if r.trace_id}))
        _, drawn, filtered = rows_asking(
            st.top_ks[slots], st.top_ps[slots], st.temps[slots], True,
            self.model.cfg.padded_vocab_size)
        d.sampler_rows_drawn = int(drawn.sum())
        d.sampler_rows_filtered = int(filtered.sum())
        self._cache.account(d, st.context_lens, st.active, 1,
                            len(st.scheduler.active))
        self.sample_draw_steps += d.sampler_rows_drawn > 0
        self.sample_sort_steps += d.sampler_rows_filtered > 0

    def _launch_step(self, st: _EngineState, d: DispatchRecord, step,
                     *per_step):
        """Launch ``step`` (the decode or the verify program) on
        ``st.pages``, which the program OWNS: the arrays it is given are
        deleted when it returns and ``st.pages`` becomes the pool it
        gives back, under ``pool_lock`` so that no reader off this
        thread finds the pool between the two.  ``per_step`` are the
        host's arrays that change every step; what does not is already
        on the device (``_resident``), and the key chain the program
        gives back stays there as the next launch's.  Returns the
        step's tokens and its per-slot ``finite`` flags (the host's own
        copy), read together with its routing histogram.  A launch that
        raises in between leaves ``st.pages`` consumed
        (``_pool_consumed``), which ``_loop`` answers with a restart."""
        resident = self._resident(st, d)
        d.host_uploads += _host_arrays(per_step)
        with st.pool_lock:
            tokens, st.pages, st.key_chain, finite, routing = step(
                self.params, st.pages, *per_step, *resident)
        d.mark("dispatch")
        tokens, finite, routing = self._read(d, tokens, finite, routing)
        d.mark("fetch")
        self._cache.account_routing(d, routing)
        return tokens, finite.copy()

    def _run_decode(self, st: _EngineState, slots: List[int],
                    d: DispatchRecord) -> None:
        bs = self.config.block_size
        for s in slots:
            self._writable(st, s, int(st.context_lens[s]) // bs)
        decoding = [r for r in (st.scheduler.active.get(s) for s in slots)
                    if r is not None and r.state == RequestState.DECODE]
        self._note_batch(st, d, slots, decoding)
        self._window_advance(
            st, d, [(s, int(st.context_lens[s]), 1) for s in slots])
        d.mark("build_inputs")
        next_tokens, finite = self._launch_step(
            st, d, self._decode_step, st.last_tokens, st.context_lens,
            self._cache.tables(st.blocks), st.active)
        if st is not self._st:
            self.loop_profiler.finish(d)
            return          # engine restarted mid-dispatch: stale state
        inj = self.fault_injector if self.warmed_up else None
        if slots and inj is not None \
                and inj.poison_nonfinite(self._dispatches):
            # flip only the fetched host-side flag of the lowest busy
            # slot: device state is untouched, so batch-mates are
            # trivially token-identical to an uninjected run
            finite[min(slots)] = False
        step_secs = d.wait_secs
        self.decode_secs += step_secs
        self.decode_steps += 1
        self.occupancy_sum += len(slots)
        # amortized TPOT accounting: each co-batched request pays an
        # equal share of the batched step — its true marginal latency,
        # not the whole step (which double-counts at high occupancy)
        share = step_secs / max(len(decoding), 1)
        for req in decoding:
            req.decode_amortized_secs += share
            req.decode_tokens += 1
        for s in slots:
            req = st.scheduler.active.get(s)
            if req is None or req.state != RequestState.DECODE:
                continue
            if not finite[s]:
                # slot-level fault isolation: only the poisoned slot is
                # evicted; the loop continues with its batch-mates
                self._evict_nonfinite(st, req)
                continue
            # the step wrote last_tokens[s] into the cache at
            # context_lens[s] and sampled the next token
            st.context_lens[s] += 1
            tok = int(next_tokens[s])
            st.last_tokens[s] = tok
            sp = req.sampling
            if sp.top_p_decay > 0.0:
                st.top_ps[s] = sp.top_p_at(len(req.out_tokens) + 1)
                st.stale("top_ps")
            self._emit_and_check(st, req, tok)
        self.loop_profiler.finish(d)

    def _run_verify(self, st: _EngineState, slots: List[int],
                    disp: DispatchRecord) -> None:
        """Speculative decode step: draft on the host (prompt-lookup
        per slot), verify all slots in one [S, K+1] forward, then commit
        1..K+1 tokens per slot with rejected drafts rolled back by a
        cursor decrement (the pages are per-slot append-only; the next
        step's scatter overwrites the stale tail)."""
        cfg = self.config
        K = self.draft_k
        bs = cfg.block_size
        S = cfg.num_slots
        decoding = [r for r in (st.scheduler.active.get(s) for s in slots)
                    if r is not None and r.state == RequestState.DECODE]
        # host drafting: each exact-greedy slot proposes from its OWN
        # history, clamped so accepted drafts + the bonus token can
        # never overshoot max_new_tokens (satisfying the scheduler's +K
        # page reservation as a side effect); sampled-temperature slots
        # draft 0 and decode normally inside the same program
        draft_tokens = np.zeros((S, K), np.int32)
        draft_lens = np.zeros(S, np.int32)
        for req in decoding:
            sp = req.sampling
            if not sp.greedy:
                continue
            d = lookup_draft(req.tokens,
                             draft_budget(K, sp.max_new_tokens,
                                          len(req.out_tokens)))
            if d:
                draft_lens[req.slot] = len(d)
                draft_tokens[req.slot, :len(d)] = d
        disp.mark("draft")
        vlens = np.where(st.active > 0, 1 + draft_lens, 0).astype(np.int32)
        verify_tokens = np.zeros((S, K + 1), np.int32)
        verify_tokens[:, 0] = st.last_tokens
        verify_tokens[:, 1:] = draft_tokens
        for s in slots:
            ctx = int(st.context_lens[s])
            last = ctx + max(int(vlens[s]), 1) - 1
            for bi in range(ctx // bs, last // bs + 1):
                self._writable(st, s, bi)
        self._note_batch(st, disp, slots, decoding)
        disp.drafted = int(draft_lens.sum())
        self._cache.account(disp, st.context_lens, vlens, K + 1,
                            len(st.scheduler.active))
        disp.mark("build_inputs")
        # the key discipline is the plain decode step's (_split_keys):
        # exactly one split per decoding slot per step, so a sampled
        # slot's stream is bit-identical spec-on vs spec-off
        emit, finite = self._launch_step(
            st, disp, self._verify_step, verify_tokens, st.context_lens,
            self._cache.tables(st.blocks), vlens)
        if st is not self._st:
            self.loop_profiler.finish(disp)
            return          # engine restarted mid-dispatch: stale state
        inj = self.fault_injector if self.warmed_up else None
        if slots and inj is not None \
                and inj.poison_nonfinite(self._dispatches):
            finite[min(slots)] = False
        step_secs = disp.wait_secs
        self.decode_secs += step_secs
        self.decode_steps += 1
        self.occupancy_sum += len(slots)
        share = step_secs / max(len(decoding), 1)
        for req in decoding:
            req.decode_amortized_secs += share
        for s in slots:
            req = st.scheduler.active.get(s)
            if req is None or req.state != RequestState.DECODE:
                continue
            if not finite[s]:
                self._evict_nonfinite(st, req)
                continue
            L = int(draft_lens[s])
            g = emit[s]
            # accept rule: longest prefix with draft_i == the token the
            # verified logits emit at position i — exactly the token the
            # plain path would have produced, because row i's logits are
            # exact whenever drafts 1..i all matched
            a = 0
            while a < L and int(draft_tokens[s, a]) == int(g[a]):
                a += 1
            self.drafted_tokens += L
            req.spec_drafted += L
            sp = req.sampling
            committed = 0
            for i in range(a + 1):
                # advance the cursor BEFORE emitting: _retire (via a
                # stop/length finish inside _emit_and_check) reads
                # context_lens[s] as the written-KV count
                st.context_lens[s] += 1
                tok = int(g[i])
                st.last_tokens[s] = tok
                req.decode_tokens += 1
                committed += 1
                if sp.top_p_decay > 0.0:
                    st.top_ps[s] = sp.top_p_at(len(req.out_tokens) + 1)
                    st.stale("top_ps")
                self._emit_and_check(st, req, tok)
                if req.state == RequestState.DONE:
                    break       # stop token mid-chain: drop the rest
            # committed - 1 of the commits were drafts (the bonus token
            # is the engine's own); context_lens now points past the
            # last committed token — rejected drafts' KV beyond it is
            # stale but unreachable (valid_lens gates every read)
            self.accepted_tokens += committed - 1
            req.spec_accepted += committed - 1
        self.loop_profiler.finish(disp)

    # -- completion -----------------------------------------------------

    def _evict_nonfinite(self, st: _EngineState, req: Request) -> None:
        """Non-finite sentinel tripped for this slot: structured failure
        (HTTP maps ``finish_reason="nonfinite"`` to a 500) and eviction
        WITHOUT registering its pages — KV written by a poisoned forward
        pass must never enter the prefix cache."""
        self.slots_evicted_nonfinite += 1
        tracing.instant("slot_evicted_nonfinite", "serve", request=req.id,
                        slot=req.slot, trace=req.trace_id)
        req._finish(FINISH_NONFINITE,
                    error="non-finite logits detected for this slot")
        self._retire(st, req)

    def _emit_and_check(self, st: _EngineState, req: Request,
                        tok: int) -> None:
        prev = (req.out_tokens[-1] if req.out_tokens
                else req.prompt_tokens[-1])
        req._emit_token(tok)
        self.tokens_generated += 1
        sp = req.sampling
        reason = None
        if tok == sp.eod_id or tok in sp.stop_token_ids:
            reason = FINISH_STOP
        elif (prev, tok) in sp.stop_pairs:
            reason = FINISH_STOP
        elif len(req.out_tokens) >= sp.max_new_tokens:
            reason = FINISH_LENGTH
        if reason is not None:
            req._finish(reason)
            self._retire(st, req)

    def _retire(self, st: _EngineState, req: Request) -> None:
        s = req.slot
        n_written = 0
        if s is not None:
            # tokens with KV actually on device: context_lens[s] once the
            # request reached decode (= prompt + generated - 1;
            # context_lens stays 0 through prefill), else the prefill
            # progress.  Blocks beyond that were reserved but never
            # written and go straight back to the free list.
            n_written = (int(st.context_lens[s])
                         if st.context_lens[s] > 0
                         else req.prefill_pos)
            st.active[s] = 0
        if req.finish_reason == FINISH_NONFINITE:
            n_written = 0   # poisoned KV: register nothing for reuse
        st.scheduler.evict(req, token_ids=req.chain, n_written=n_written)
        self._count_finish(req.finish_reason)
        # the request's own span, on the launches' clock: kept beside
        # them always, and written to the SpanTracer when one is there
        span = RequestSpan(
            req.id, req.trace_id, req._pc_submit, req._pc_admit,
            req._pc_first_token, time.perf_counter(),
            len(req.prompt_tokens), len(req.out_tokens), req.finish_reason)
        self.loop_profiler.record_request(span)
        tracer = tracing.get_tracer()
        if tracer is not None:
            tracer.completed(
                "request", "serve", span.submit, span.finish - span.submit,
                request=req.id, trace=req.trace_id,
                prompt_tokens=span.prompt_tokens,
                new_tokens=span.answer_tokens,
                finish_reason=req.finish_reason)
        bstats = st.blocks.stats()
        tpot = req.tpot_secs()
        record = {
            "kind": "serve", "event": "request_done",
            "request": req.id,
            "trace_id": req.trace_id,
            "prompt_tokens": len(req.prompt_tokens),
            "cached_prompt_tokens": req.cached_prompt_tokens,
            "prefill_computed_tokens":
                max(len(req.prompt_tokens) - req.cached_prompt_tokens, 0),
            "new_tokens": len(req.out_tokens),
            "decode_tokens": req.decode_tokens,
            "drafted_tokens": req.spec_drafted,
            "accepted_tokens": req.spec_accepted,
            "accept_rate": (round(req.accept_rate(), 4)
                            if req.accept_rate() is not None else None),
            "finish_reason": req.finish_reason,
            "ttft_secs": req.ttft_secs(),
            "latency_secs": req.latency_secs(),
            "tpot_secs": round(tpot, 6) if tpot is not None else None,
            "phases": req.phases(),
            "paged_kernel": self.paged_kernel,
            "prefill_kernel": self.prefill_kernel,
            "queue_depth": self.queue.depth(),
            "blocks_free": bstats["blocks_free"],
            "blocks_in_use": bstats["blocks_in_use"],
            "blocks_cached_reusable": bstats["blocks_cached_reusable"],
            "miss_cold_blocks": req.miss_cold_blocks,
            "miss_evicted_blocks": req.miss_evicted_blocks,
            "host_hit_blocks": req.host_hit_blocks,
            "swap_in_secs": round(req.swap_in_secs, 6),
        }
        stream = telemetry.get_stream()
        if stream is not None:
            stream.emit(record)
        hook = self.request_done_hook
        if hook is not None:
            try:
                hook(record)
            except Exception:
                pass    # metrics must never take down the engine loop

    def _count_finish(self, reason: Optional[str]) -> None:
        # engine loop and restart (watchdog thread) both count here
        if reason:
            with self._finished_lock:
                self.finished[reason] = self.finished.get(reason, 0) + 1

    # ------------------------------------------------------------------
    # warmup / stats
    # ------------------------------------------------------------------

    def warmup(self) -> None:
        """Compile the steady-state programs (prefill chunk, first-token
        sampler, and the decode step — the [S, K+1] verify program when
        speculative is on, the [S] plain step otherwise) with one dummy
        greedy request.  The decode/verify step and the prefill chunk
        each bake in their resolved paged-attention path (Pallas ragged
        kernel or XLA gather — static config fields), so each kernel
        compiles here exactly once.  Call before
        ``tracing.RecompileDetector.mark_steady()`` — after this, serving
        arbitrary requests triggers zero compiles."""
        assert self._thread is None, "warm up before start()"
        with tracing.startup_span("warmup"):
            self._warmup()

    def _warmup(self) -> None:
        st = self._st
        prompt = [1] * min(self.config.prefill_chunk + 1,
                           max(self.config.max_model_len - 4, 1))
        req = Request(prompt, SamplingParams(max_new_tokens=3,
                                             temperature=0.0))
        req._pc_submit = time.perf_counter()
        self.queue.put(req)
        deadline = time.monotonic() + 300.0
        # a launch that traces one of the loop's programs for the first
        # time is a child of the start-up timeline's ``warmup`` under the
        # program's name: its trace, lowering, compile or cache load and
        # its first execution
        rows = tracing.compile_ledger().programs

        def traced() -> Dict[str, int]:
            return {p: (rows.get(p) or (0,))[0] for p in self._jitted}

        named: set = set()
        while req.state != RequestState.DONE:
            before, t0 = traced(), time.perf_counter()
            if not self.step(st):
                break
            new = [p for p, n in traced().items() if n > before[p]]
            if new:
                name = next((p for p in new if p not in named), new[0])
                named.add(name)
                tracing.startup_completed(
                    "warmup." + name, t0, time.perf_counter(), traced=new)
            if time.monotonic() > deadline:
                raise TimeoutError("engine warmup did not converge")
        # compile the copy-on-write page copy (garbage -> garbage is a
        # no-op) so a later COW event can't trip the recompile detector
        if self._cache.paged:
            with tracing.startup_span("warmup.engine_cow_copy"):
                st.pages = self._copy_page(st.pages, 0, 0)
        if self.host_cache is not None:
            # compile the host-tier pair the same way: gather the
            # garbage page to host, scatter it straight back — both
            # no-ops, after which spills and swap-ins are compile-free
            with tracing.startup_span("warmup.engine_fetch_block"):
                garbage = jax.device_get(
                    self._fetch_block(st.pages, np.int32(0)))
            with tracing.startup_span("warmup.engine_host_load"):
                st.pages = self._host_load(st.pages, garbage, np.int32(0))
        jax.block_until_ready(st.pages[self._cache.first_pool])
        self.warmed_up = True
        # compile-time gaps between warmup dispatches are expected —
        # only steady-state dispatch gaps count as loop stalls
        self.loop_profiler.stall_armed = True
        tracing.instant("engine_warm", "serve")

    def _abstract_pool(self):
        """The pool in its abstract form (shapes, dtypes, placement),
        taken between launches: a decode step consumes the arrays, and
        whoever describes the pool keeps none."""
        st = self._st
        with st.pool_lock:
            return jax.tree_util.tree_map(_abstract, st.pages)

    def _program_arguments(self) -> Dict[str, tuple]:
        """What each program warm-up compiled is launched with, as the
        launches' call sites build it from the state (the shapes are
        fixed for the engine's life): the programs by name, for
        ``program_tables`` to lower from."""
        st, cfg = self._st, self.config
        S, zero = cfg.num_slots, np.int32(0)

        def placed(host):
            return jax.ShapeDtypeStruct(host.shape, host.dtype,
                                        sharding=self._beside)
        # what _resident hands a step: the host's arrays as _place lays
        # them (and as the chain comes back from a step), none read here
        resident = (*(placed(getattr(st, name)) for name in _SAMPLING),
                    placed(st.keys), placed(st.keys),
                    placed(st.keys_given))
        pool = self._abstract_pool()
        pages = jax.tree_util.tree_leaves(pool)[0]
        found = {
            "engine_prefill": (
                self.params, pool,
                np.zeros((1, cfg.prefill_chunk), np.int32), zero, zero,
                self._cache.chunk_tables(st.blocks, 0, False)),
            # a chunk's last logits: the prefill program's output
            "engine_sample_first": (
                jax.ShapeDtypeStruct(
                    (int(self.model.cfg.padded_vocab_size),), jnp.float32,
                    sharding=pages.sharding),
                st.keys[0], st.top_ks[0], st.top_ps[0], st.temps[0],
                st.ban_a[0], st.ban_b[0], zero),
        }
        if self._cache.paged:
            found["engine_cow_copy"] = (paged_kv.paged_pools(pool), zero,
                                        zero)
        if self.speculative:
            found["engine_verify"] = (
                self.params, pool,
                np.zeros((S, self.draft_k + 1), np.int32), st.context_lens,
                self._cache.tables(st.blocks), st.active) + resident
        else:
            found["engine_decode"] = (
                self.params, pool, st.last_tokens, st.context_lens,
                self._cache.tables(st.blocks), st.active) + resident
        if self.host_cache is not None:
            page = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), pool)
            found["engine_fetch_block"] = (pool, zero)
            found["engine_host_load"] = (pool, page, zero)
        return found

    def program_tables(self, sharding=None) -> Dict[str, Any]:
        """An instruction table (``hlo_collectives.ProgramTable``) of each
        program warm-up compiled, read from the program's own optimised
        text: every instruction under the name a profiler's trace prints,
        with its opcode, shapes, operands, loops, ``op_name``, scope and
        role (``kv_pool`` for what moves an array of this engine's pool).
        Built when first asked for and never before: each program is
        lowered again from the abstract form of ``_program_arguments``
        (a hit in jit's own cache, the executable that ran; a backend
        compile where that cache has been dropped), so nothing here is on
        a launch's path nor in warm-up, and ``stats()['programs']`` is
        None until somebody asks.  The loop profiler holds the same
        tables beside the launch ring
        (``live_profilers()[0].program_tables()``).  ``sharding`` lowers
        for another placement than the one that ran (a described chip)
        and keeps nothing; an engine that has not warmed up has none."""
        if self._program_tables is not None and sharding is None:
            return self._program_tables
        if not self.warmed_up:
            return {}
        pages = self._abstract_pool()
        pool = paged_kv.array_shapes(pages)
        state = paged_kv.state_shapes(pages)
        tables = {}
        for name, args in self._program_arguments().items():
            args = jax.tree_util.tree_map(_abstract, args)
            if sharding is not None:
                args = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=sharding), args)
            text = self._jitted[name].lower(*args).compile().as_text()
            tables[name] = hlo_collectives.ProgramTable(
                name, hlo_collectives.instructions(text), pool=pool,
                state=state)
        if sharding is None:
            self._program_tables = self.loop_profiler.programs = tables
        return tables

    def estimate_wait_secs(self) -> float:
        """Rough queue wait for a newly rejected request: queued depth
        times mean per-request engine time, divided across slots.  Cheap
        and monotone in load — meant for 429 bodies, not SLOs."""
        with self._finished_lock:
            done = sum(self.finished.values())
        if done <= 0:
            return 1.0
        per_req = (self.prefill_secs + self.decode_secs) / done
        return round(self.queue.depth() * per_req
                     / max(self.config.num_slots, 1), 3)

    def stats(self) -> Dict[str, Any]:
        s: Dict[str, Any] = dict(self.scheduler.stats())
        with self._finished_lock:
            finished = dict(self.finished)
        dec = max(self.decode_steps, 1)
        loop = self.loop_profiler.stats()
        totals = self.loop_profiler.totals()
        s.update({
            "decode_steps": self.decode_steps,
            "sample_draw_steps": self.sample_draw_steps,
            "sample_sort_steps": self.sample_sort_steps,
            "prefill_chunks": self.prefill_chunks,
            # of those, the chunks that ended their context and ran the
            # output head (one a request prefilled to its end)
            "prefill_heads": totals["prefill_head_rows"],
            "tokens_generated": self.tokens_generated,
            "prefill_tokens_submitted": self.prefill_tokens_submitted,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_tokens_cached": self.prefill_tokens_cached,
            "prefill_tokens_cached_at_prefill":
                self.prefill_tokens_cached_at_prefill,
            "mean_batch_occupancy": self.occupancy_sum / dec,
            "prefill_secs": round(self.prefill_secs, 6),
            "decode_secs": round(self.decode_secs, 6),
            "finished": finished,
            "warmed_up": self.warmed_up,
            "paged_kernel": self.paged_kernel,
            "prefill_kernel": self.prefill_kernel,
            "speculative": self.speculative,
            "draft_k": self.draft_k,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            # what the launches counted, summed where they finish (the
            # profiler's totals); those the 'loop' block carries stay there
            **{f: n for f, n in totals.items() if f not in loop},
            **({"moe_expert_tiles": self.moe_expert_tiles}
               if self.moe_expert_tiles else {}),
            "engine_restarts": self.engine_restarts,
            "slots_evicted_nonfinite": self.slots_evicted_nonfinite,
            "loop": loop,
            "cache": self.cache_observatory.stats(),
            # the programs' instruction tables, once program_tables() has
            # been asked for; beside them the pool's bytes, which
            # kv_pool_copy_bytes_per_launch is held against
            "kv_pool_bytes": self.kv_pool_bytes,
            # the start-up timeline's summary, as its line printed it
            "startup": tracing.startup_summary(),
            "programs": (None if self._program_tables is None else
                         {name: t.summary()
                          for name, t in self._program_tables.items()}),
        })
        return s
