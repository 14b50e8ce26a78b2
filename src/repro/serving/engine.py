"""Live serving engine: FaST-GShare data plane over real JAX executors.

This is the paper's serving stack made real on this container, one engine
per node:

* **Model sharing (§3.5)** — N instances of a function share ONE param
  pytree through the ``ModelStore``; the runtime never copies weights.
* **FaST-Manager (§3.3)** — every instance's dispatch loop is gated by the
  node's ``TokenScheduler``; wall-clock step times feed ``Q_used`` exactly
  as the paper's CUDA-event accounting does (DESIGN.md §2).
* **Continuous (slot-level) batching** — each ``FunctionInstance`` owns a
  fixed pool of ``max_batch`` decode slots backed by a persistent per-slot
  KV cache (``Model.init_slot_cache``).  A finished request frees its slot
  *immediately*; queued requests are admitted mid-flight by prefilling
  them individually and merging their cache entries into the live decode
  batch at the freed slot index (``Model.merge_slot``).  Token-granted
  decode steps therefore stay full whenever there is queued work — the
  property the paper's throughput wins depend on.  ``batching="static"``
  keeps the old retire-together semantics as a reference implementation
  (the equivalence tests decode both ways and compare token streams).
* **Block-paged KV (``batching="paged"``)** — the slot pool's dense
  ``max_len`` rows are replaced by physical blocks of ``block_size``
  tokens handed out by a ``KVPageAllocator``; admission budgets FREE
  BLOCKS (a request needs ``ceil((prompt + new_tokens - 1)/block_size)``)
  instead of just free slots, and a finished/drained request releases its
  blocks immediately, so short requests stop stranding the memory the
  MRA/``MemoryModel`` admission charged for them.  Decode walks per-slot
  block tables (``Model.decode_step_paged``); token streams are
  bit-identical to the dense path.  See ``serving/README.md`` for the
  block-table layout.
* **Sync-free decode hot path (``fused=True``, the default)** — one decode
  round is a single donated, fused jitted call: the greedy sampler runs on
  device (``Model.decode_step_tokens`` returns ``(B,)`` int32 tokens, the
  ``(B, V)`` logits never cross to the host), the KV pool / token vector /
  paged position vector are donated so XLA updates them in place instead
  of copying the cache every round, and the paged block tables + positions
  stay device-resident (host mirrors are only touched on admit / release /
  migrate and re-uploaded once when dirty).  Each instance splits a step
  into ``dispatch_step`` (enqueue the round, no host pull) and
  ``sync_step`` (ONE host synchronisation for everything the pass
  dispatched), which lets ``ServingEngine.pump`` dispatch every co-located
  instance's round before pulling any of their results — N pods pipeline
  on one device instead of ping-ponging through Python.  ``fused=False``
  keeps the old host-side argmax path as the bit-identical reference.

Topology: a ``ServingEngine`` is one node; ``repro.serving.frontend``
routes requests across several engines (join-shortest-queue) and places
functions onto nodes with the same MRA + memory-model admission the
simulator uses, so the live path mirrors ``repro.core.cluster`` end to
end.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.manager import TokenScheduler
from repro.core.model_sharing import ModelStore
from repro.core.resources import Alloc
from repro.core.slo import SLORecorder
from repro.distributed.sharding import serve_pspec, shard_put, use_mesh
from repro.models.model import Model, default_kv_blocks
from repro.serving.paging import (NULL_BLOCK, KVPageAllocator, PageTable,
                                  blocks_needed, prompt_digests)
from repro.serving.speculative import (GREEDY, SamplingConfig, SpecConfig,
                                       spec_round_continuous,
                                       spec_round_paged)


def _bucket_len(n: int) -> int:
    """Smallest power of two >= n (prefill padding bucket)."""
    return 1 << max(n - 1, 0).bit_length()


def _executor(model: Model, key: tuple, build) -> Any:
    """Per-model shared jit wrapper: ``jax.jit`` caches compiled
    executables per *wrapper object*, so per-instance wrappers would pay
    a fresh trace + compile on every deployment.  Sharing them across
    instances (keyed on the model, stored on it so the cache dies with
    it) is what makes a warm node warm in the cold-start sense: it holds
    the function's compiled executors, not just its weights.  Donation
    is per-call semantics, so shared donated wrappers are safe.

    ``build`` must jit a FRESH function object (a lambda), never a bound
    method directly: jax shares its trace cache across jit wrappers of
    the same underlying function, and a sharded pod's mesh constraints
    are baked into the jaxpr at trace time — a bound-method trace from
    one device group would silently serve every other group's executor
    and fail on the first mismatched device set."""
    cache = model.__dict__.setdefault("_jit_executors", {})
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = build()
    return fn


# Model-independent: scatter one sampled token into the donated vector.
_SET_TOK = jax.jit(lambda t, s, v: t.at[s].set(v), donate_argnums=(0,))


def _on_device(method):
    """Run an instance method with its node's device as JAX's default, so
    every array the step uploads (prompts, tables, positions, a fresh KV
    pool) lands next to the weights instead of on ``jax.devices()[0]``."""
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        if self.device is None:
            return method(self, *args, **kwargs)
        with jax.default_device(self.device):
            return method(self, *args, **kwargs)
    return wrapper


def per_device_bytes(*trees: Any) -> dict[int, int]:
    """Resident bytes per device id across ``trees`` (``None`` entries are
    skipped), via each leaf's ``addressable_shards`` — so a tensor-parallel
    leaf charges each device only its shard, while a replicated leaf
    charges its full size on every device.  The benchmark's per-shard HBM
    high-watermark accounting."""
    out: dict[int, int] = {}
    for tree in trees:
        if tree is None:
            continue
        for leaf in jax.tree_util.tree_leaves(tree):
            if not isinstance(leaf, jax.Array):
                continue
            for shard in leaf.addressable_shards:
                d = int(shard.device.id)
                out[d] = out.get(d, 0) + int(shard.data.nbytes)
    return out


@dataclasses.dataclass
class ServeRequest:
    req_id: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 8
    submitted_at: float = 0.0
    tokens_out: list = dataclasses.field(default_factory=list)
    done: bool = False
    finished_at: float = 0.0
    # SLO lifecycle (inert by default): absolute deadline on the engine
    # clock (None = no deadline), the tier it was admitted under, a typed
    # outcome when the request terminates without completing
    # (shed/expired/rejected/failed — see repro.core.slo), and how many
    # times it has been re-routed after a failure.
    deadline: Optional[float] = None
    tier: str = "best_effort"
    outcome: Optional[str] = None
    attempts: int = 0


class FunctionInstance:
    """One FaSTPod-equivalent: jitted prefill/decode with shared weights.

    ``batching="continuous"`` (default): a fixed pool of ``max_batch``
    decode slots; every step first admits queued requests into free slots
    (chunked prefill + slot merge), then advances all occupied slots one
    token.  ``batching="static"``: the legacy batch that only re-fills
    once every member finishes — kept as the reference semantics.

    ``fused=True`` (default for the slot modes) runs the sync-free hot
    path: a step is dispatched by ``dispatch_step`` (no host round-trip)
    and completed by ``sync_step`` (one blocking pull for the whole pass);
    ``run_step`` chains the two for callers that want the old synchronous
    protocol.  ``fused=False`` restores the host-side argmax reference —
    token streams are bit-identical either way.
    """

    def __init__(self, inst_id: str, model: Model, store: ModelStore,
                 weights_key: str, alloc: Alloc, *, max_batch: int = 4,
                 max_len: int = 64, batching: str = "continuous",
                 prefill_buckets: bool = True, block_size: int = 16,
                 n_kv_blocks: Optional[int] = None, fused: bool = True,
                 prefix_sharing: bool = True,
                 sampling: Optional[SamplingConfig] = None,
                 speculate: Optional[SpecConfig] = None,
                 draft_model: Optional[Model] = None,
                 draft_key: Optional[str] = None,
                 mesh: Optional[Any] = None,
                 device: Optional[Any] = None):
        if batching not in ("continuous", "static", "paged"):
            raise ValueError(f"unknown batching mode {batching!r}")
        if sampling is not None and batching == "static":
            raise ValueError("stochastic sampling requires a slot batching "
                             "mode (continuous/paged)")
        if mesh is not None and speculate is not None:
            raise ValueError(
                "speculate cannot ride a sharded pod: the draft/verify "
                "round is not tensor-parallel (FunctionSpec forbids it)")
        # Tensor-parallel pod: every executor runs under this mesh so the
        # models' named() constraints bind at trace time, and the executor
        # cache key gets a mesh suffix.  ``key + ()`` IS ``key``, so a
        # shards=1 instance hits the exact single-device cache entries —
        # no re-trace, byte-identical dispatch.
        self.mesh = mesh
        # The node's device (None: JAX's default): uploads and the KV pool
        # go there.  A sharded pod places by its mesh instead.
        self.device = device if mesh is None else None
        self._mkey = (() if mesh is None else
                      ("tp", tuple(int(d.id) for d in mesh.devices.flat)))

        def _jit(owner: Model, key: tuple, build) -> Any:
            fn = _executor(owner, key + self._mkey, build)
            if mesh is None:
                return fn

            def sharded(*a, _fn=fn, **kw):
                with use_mesh(mesh):
                    return _fn(*a, **kw)
            return sharded

        self.inst_id = inst_id
        self.model = model
        self.alloc = alloc
        self.max_batch = max_batch
        self.max_len = max_len
        self.batching = batching
        self.fused = fused and batching != "static"
        self.store = store
        self.weights_key = weights_key
        self.params = store.get(weights_key)  # shared, zero-copy
        self.queue: deque[ServeRequest] = deque()
        self._prefill = _jit(model, ("prefill", max_len), lambda:
                                  jax.jit(lambda p, t: model.prefill(
                                      p, t, max_len=max_len)))
        # Bucketed chunked admission: prompts are right-padded to power-of-
        # two buckets so the jitted prefill sees O(log max_len) distinct
        # shapes instead of one per prompt length (each a recompile).
        self.bucketed = (batching in ("continuous", "paged")
                         and prefill_buckets
                         and model.supports_bucketed_prefill())
        self._prefill_len = _jit(model, ("prefill_len", max_len),
                                      lambda: jax.jit(
                                          lambda p, t, n: model.prefill(
                                              p, t, max_len=max_len,
                                              length=n))
                                      ) if self.bucketed else None
        self._decode = _jit(model, ("decode",),
                                 lambda: jax.jit(lambda *a: model.decode_step(*a)))
        # Fused executors: the decode round samples on device and returns
        # (B,) int32 tokens; the token vector and the whole KV pool are
        # DONATED — after dispatch the old buffers are dead and XLA writes
        # the new round in place (no per-round cache copy).  Never alias a
        # donated buffer after dispatch (serving/README.md "Hot path").
        self._decode_tok = _jit(model, ("decode_tok",), lambda:
                                     jax.jit(lambda *a: model.decode_step_tokens(*a),
                                             donate_argnums=(1, 2)))
        self._greedy = _jit(model, ("greedy",),
                                 lambda: jax.jit(lambda *a: model.sample_greedy(*a)))
        self._set_tok = _SET_TOK
        # The slot pool is donated on merge/append too: admitting a request
        # scatters its prefill entry into the pool in place.
        self._merge = _jit(model, ("merge",), lambda:
                                jax.jit(lambda *a: model.merge_slot(*a),
                                        donate_argnums=(0,)))
        self.steps = 0
        self.retired = False  # draining: no new routing, slots finish
        self.paused = False   # migrating: no admission, no decode
        # Wall-clock of the FIRST token this instance ever landed on a
        # request — the cold-start tier's time-to-first-token anchor.
        self.first_token_at: Optional[float] = None
        # continuous state: slot i holds the request decoding in cache row i.
        self.slots: list[Optional[ServeRequest]] = [None] * max_batch
        self._slot_tok = np.zeros((max_batch,), np.int32)
        self.cache: Optional[Any] = None  # slot pool / static batch cache
        # static state
        self.active: list[ServeRequest] = []
        self.refills = 0  # mid-flight slot admissions (continuous only)
        self.last_fill = 0  # slots that did work in the latest step
        # Prefix sharing (paged only): admission matches prompt-block
        # digests against resident pages; divergence resolves by COW.
        self.prefix_sharing = prefix_sharing and batching == "paged"
        self.shared_block_hits = 0  # resident blocks mapped, not re-written
        self.cow_count = 0          # divergent appends resolved by a copy
        # -- sync-free hot-path state (fused modes) -------------------------
        self.sync_count = 0  # host synchronisation points (telemetry)
        self.uploads = 0     # paged table/pos uploads (dirty-flag telemetry)
        self._slot_tok_dev: Optional[jax.Array] = None  # (B,) device tokens
        # Deferred results of the in-flight pass: (req, (1,) device token,
        # slot or None for done-at-prefill) plus the decode round's
        # ((B,) device tokens, active-slot snapshot).
        self._pending_prefill: list[tuple[ServeRequest, Any,
                                          Optional[int]]] = []
        self._round: Optional[tuple[Any, list[int]]] = None
        self._host_finished: list[ServeRequest] = []  # non-fused stash
        # paged state: host-side block tables + positions are the MIRRORS;
        # the jitted decode consumes device-resident copies that are only
        # re-uploaded when admit/release/migrate dirtied the host side.
        if batching == "paged":
            if not model.supports_paged():
                raise ValueError(
                    f"{model.cfg.name}: batching='paged' needs a full-cache "
                    f"dense/moe config")
            if block_size <= 0 or max_len % block_size:
                raise ValueError(
                    "block_size must be positive and divide max_len")
            self.block_size = block_size
            self.blocks_per_seq = max_len // block_size
            n_blocks = (n_kv_blocks if n_kv_blocks is not None
                        else default_kv_blocks(max_batch, max_len,
                                               block_size))
            self._block_bytes = model.kv_block_bytes(block_size)
            self.allocator = KVPageAllocator(n_blocks, block_size,
                                             block_bytes=self._block_bytes)
            self.pages = PageTable(self.allocator)
            self._tables = np.full((max_batch, self.blocks_per_seq),
                                   NULL_BLOCK, np.int32)
            self._pos = np.zeros((max_batch,), np.int32)
            self._decode_paged = _jit(
                model, ("decode_paged",),
                lambda: jax.jit(lambda *a: model.decode_step_paged(*a)))
            self._decode_paged_tok = _jit(
                model, ("decode_paged_tok",),
                lambda: jax.jit(lambda *a: model.decode_step_paged_tokens(*a),
                                donate_argnums=(1, 2, 4)))
            self._append = _jit(
                model, ("append",),
                lambda: jax.jit(lambda *a: model.append_paged(*a), donate_argnums=(0,)))
            self._copy_block = _jit(
                model, ("copy_block",),
                lambda: jax.jit(lambda *a: model.copy_block(*a), donate_argnums=(0,)))
            self._tables_dev: Optional[jax.Array] = None
            self._pos_dev: Optional[jax.Array] = None
            self._active_dev: Optional[jax.Array] = None
            self._state_dirty = True
        # -- stochastic sampling + speculative decoding ---------------------
        # The PRNG key is device state threaded through the fused round and
        # donated like the token vector; the fused=False reference replays
        # the identical split sequence eagerly, so sampled token streams
        # diff bit-identical between the paths.
        self.sampling = sampling
        self.speculate = speculate
        self.draft_model = draft_model
        self.draft_key = draft_key
        self.draft_params: Optional[Any] = None
        self.dcache: Optional[Any] = None  # draft slot-cache side pool
        self.spec_proposed = 0  # draft tokens proposed (telemetry)
        self.spec_accepted = 0  # draft tokens accepted (telemetry)
        self._round_spec: Optional[tuple[Any, Any]] = None
        self._key_dev: Optional[jax.Array] = None
        if sampling is not None or speculate is not None:
            seed = sampling.seed if sampling is not None else speculate.seed
            self._key_dev = jax.device_put(jax.random.PRNGKey(seed),
                                           self.device)
        if sampling is not None:
            self._sample = _jit(
                model, ("sample", sampling),
                lambda: jax.jit(lambda l, k: model.sample_tokens(l, k,
                                                                 sampling)))
            self._decode_tok_s = _jit(
                model, ("decode_tok_sampled", sampling),
                lambda: jax.jit(
                    lambda p, t, c, k: model.decode_step_tokens(
                        p, t, c, key=k, sampling=sampling),
                    donate_argnums=(1, 2, 3)))
            if batching == "paged":
                self._decode_paged_tok_s = _jit(
                    model, ("decode_paged_tok_sampled", sampling),
                    lambda: jax.jit(
                        lambda p, t, c, tb, pos, act, k:
                        model.decode_step_paged_tokens(
                            p, t, c, tb, pos, act, key=k, sampling=sampling),
                        donate_argnums=(1, 2, 4, 6)))
        if speculate is not None:
            if not self.fused or batching == "static":
                raise ValueError(
                    "speculate requires the fused continuous/paged hot path "
                    "(the draft/verify loop is a single donated round)")
            if not model.supports_speculative():
                raise ValueError(
                    f"{model.cfg.name}: speculative verify needs a "
                    f"full-cache dense/moe target (no int8 KV)")
            if draft_model is None or draft_key is None:
                raise ValueError("speculate needs a draft model + weights "
                                 "key (engine.deploy builds them)")
            if not draft_model.supports_speculative():
                raise ValueError(
                    f"{draft_model.cfg.name}: the draft must be a "
                    f"full-cache dense/moe config")
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError("draft and target must share vocab_size")
            self.draft_params = store.get(draft_key)
            samp = sampling if sampling is not None else GREEDY
            build = (spec_round_paged if batching == "paged"
                     else spec_round_continuous)
            donate = (2, 3, 4, 6, 8) if batching == "paged" else (2, 3, 4, 5)
            self._spec_round = _jit(
                model, ("spec_round", batching, speculate.k, samp,
                        draft_model.cfg.name),
                lambda: jax.jit(build(model, draft_model, speculate.k, samp),
                                donate_argnums=donate))
            self._dprefill = _jit(
                draft_model, ("prefill", max_len),
                lambda: jax.jit(lambda p, t: draft_model.prefill(
                    p, t, max_len=max_len)))
            self._dprefill_len = _jit(
                draft_model, ("prefill_len", max_len),
                lambda: jax.jit(lambda p, t, n: draft_model.prefill(
                    p, t, max_len=max_len, length=n))
            ) if self.bucketed else None
            self._dmerge = _jit(
                draft_model, ("merge",),
                lambda: jax.jit(draft_model.merge_slot, donate_argnums=(0,)))

    def close(self) -> None:
        if self.batching == "paged":
            self.pages.release_all()  # defensive: drained closes freed all
        if self.draft_params is not None:
            self.store.put_back(self.draft_key)
        self.store.put_back(self.weights_key)

    # -- KV accounting -----------------------------------------------------

    def kv_bytes_in_use(self) -> int:
        """Physical KV bytes currently held by live requests (paged) or
        reserved by the allocated pool (dense slot modes)."""
        if self.batching == "paged":
            return self.pages.bytes_in_use(self._block_bytes)
        return (self.model.dense_kv_bytes(self.max_batch, self.max_len)
                if self.cache is not None else 0)

    def dense_kv_reserved(self) -> int:
        """What the dense slot pool would reserve for this instance's
        capacity — the baseline the paged pool is measured against."""
        return self.model.dense_kv_bytes(self.max_batch, self.max_len)

    @property
    def kv_bytes_peak(self) -> int:
        """Peak physical KV bytes.  Paged: the allocator's block
        high-watermark times block bytes — updated at every allocation
        instead of sampled once per dispatch (the old sampling could miss
        a transient peak between steps), and consistent with refcounted
        sharing: a block mapped by N sequences is one physical block,
        charged once.  Dense modes report the slot-pool reservation."""
        if self.batching != "paged":
            return self.dense_kv_reserved() if self.cache is not None else 0
        return self.allocator.bytes_high_watermark

    def kv_bytes_saved(self) -> int:
        """Bytes prefix sharing is saving right now vs the unshared paged
        plane (extra references minus reserved COW spares, in bytes)."""
        if self.batching != "paged":
            return 0
        return self.pages.bytes_saved(self._block_bytes)

    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active() > 0

    def n_active(self) -> int:
        if self.batching == "static":
            return len(self.active)
        return sum(1 for r in self.slots if r is not None)

    def load(self) -> int:
        """Queue depth + occupied slots (join-shortest-queue metric)."""
        return len(self.queue) + self.n_active()

    def acceptance_rate(self) -> float:
        """Measured draft-token acceptance fraction (0 when the instance
        is not speculating or has not completed a round yet)."""
        if not self.spec_proposed:
            return 0.0
        return self.spec_accepted / self.spec_proposed

    def _clip_tok(self, tok: np.ndarray) -> np.ndarray:
        return np.minimum(tok, self.model.cfg.vocab_size - 1)

    def _mark_first_token(self) -> None:
        """Record the instant the instance's first token became visible
        host-side (every token-landing path calls this)."""
        if self.first_token_at is None:
            self.first_token_at = time.perf_counter()

    # -- device-resident decode state (fused path) --------------------------

    def _tok_dev(self) -> jax.Array:
        """Device-resident per-slot token vector; re-uploaded from the host
        mirror only after migration touched it (``None`` invalidates)."""
        if self._slot_tok_dev is None:
            self._slot_tok_dev = jnp.asarray(self._slot_tok)
        return self._slot_tok_dev

    def _upload_paged_state(self) -> None:
        """Push dirtied host mirrors (tables / positions / active mask) to
        the device — once per admit/release/migrate burst, NOT per round."""
        mask = np.zeros((self.max_batch,), np.int32)
        for slot, req in enumerate(self.slots):
            if req is not None:
                mask[slot] = 1
        self._tables_dev = jnp.asarray(self._tables)
        self._pos_dev = jnp.asarray(self._pos)
        self._active_dev = jnp.asarray(mask)
        self._state_dirty = False
        self.uploads += 1

    def _init_cache(self) -> Any:
        """Fresh slot/paged KV pool, placed on the pod's mesh when the
        instance is sharded: kv-heads split over the tensor axis when they
        divide it, everything else replicated — the bitwise-safe default
        (no cross-device reduction touches the logits).  The sequence-
        sharded slab layout is the opt-in ``distributed.seqshard`` seam."""
        if self.batching == "paged":
            cache = self.model.init_paged_cache(self.allocator.n_blocks,
                                                self.block_size)
            if self.mesh is not None:
                cache = shard_put(
                    cache, self.model.paged_cache_names(
                        self.allocator.n_blocks, self.block_size), self.mesh)
            return cache
        cache = self.model.init_slot_cache(self.max_batch, self.max_len)
        if self.mesh is not None:
            names = dict(self.model.cache_names(self.max_batch,
                                                self.max_len))
            names["pos"] = (None,)  # slot pool pos is (n_slots,), not ()
            cache = shard_put(cache, names, self.mesh)
        return cache

    def hbm_bytes_by_device(self) -> dict[int, int]:
        """Per-device resident bytes of this instance's weights + KV pool
        (+ draft side pool), by ``addressable_shards`` — the per-shard HBM
        high-watermark a sharded pod is benchmarked on."""
        return per_device_bytes(self.params, self.cache, self.draft_params,
                                self.dcache)

    # -- continuous path ---------------------------------------------------

    def _prefill_one(self, prompt: np.ndarray):
        """Prefill one prompt, right-padded to its bucket when enabled."""
        n = int(prompt.shape[0])
        if self.bucketed and n < self.max_len:
            pl = min(_bucket_len(n), self.max_len)
            if pl > n:
                padded = np.zeros((pl,), np.int32)
                padded[:n] = prompt
                prompt = padded
            return self._prefill_len(self.params,
                                     jnp.asarray(prompt[None], jnp.int32),
                                     jnp.int32(n))
        return self._prefill(self.params, jnp.asarray(prompt[None], jnp.int32))

    def _dprefill_one(self, prompt: np.ndarray):
        """Draft-model prefill for speculative admission (same bucketing
        discipline as the target's)."""
        n = int(prompt.shape[0])
        if self.bucketed and n < self.max_len:
            pl = min(_bucket_len(n), self.max_len)
            if pl > n:
                padded = np.zeros((pl,), np.int32)
                padded[:n] = prompt
                prompt = padded
            return self._dprefill_len(self.draft_params,
                                      jnp.asarray(prompt[None], jnp.int32),
                                      jnp.int32(n))
        return self._dprefill(self.draft_params,
                              jnp.asarray(prompt[None], jnp.int32))

    def _admit_draft(self, slot: int, req: ServeRequest) -> None:
        """Prefill the draft model and merge its entry into the draft slot
        cache — both async enqueues, sharing the pass's single sync."""
        _, dentry = self._dprefill_one(req.prompt)
        if self.dcache is None:
            self.dcache = self.draft_model.init_slot_cache(self.max_batch,
                                                           self.max_len)
        self.dcache = self._dmerge(self.dcache, dentry, jnp.int32(slot))

    def _spec_k(self, max_new_tokens: int) -> int:
        """Extra KV rows a speculating request can write past the plain
        ``prompt + max_new - 1``: the last verify window starts at most at
        row ``prompt + max_new - 2`` and writes k rows beyond it.  Zero
        for requests that finish at prefill (they never enter a round)."""
        if self.speculate is None or max_new_tokens <= 1:
            return 0
        return self.speculate.k

    def _kv_rows_needed(self, req: ServeRequest) -> int:
        """KV rows a request writes over its lifetime: the prompt plus one
        row per decode round (the final token is emitted, never cached),
        plus the speculation margin for the verify window's overhang."""
        return (int(req.prompt.shape[0]) + req.max_new_tokens - 1
                + self._spec_k(req.max_new_tokens))

    def _plan_paged_admission(self, req: ServeRequest
                              ) -> tuple[int, tuple]:
        """Blocks a paged admission must ALLOCATE for ``req``, plus its
        prefix-sharing plan ``(full_digests, tail_digest, shared_full,
        tail_block)``.

        The charge is ``blocks_needed - matched full blocks``: a shared
        full block costs nothing (it is resident and immutable), while a
        shared prompt-tail block trades its block for a reserved COW
        spare — memory-neutral, charged as one block either way.
        """
        total = blocks_needed(self._kv_rows_needed(req), self.block_size)
        if not self.prefix_sharing:
            return total, ([], None, [], None)
        full, tail_digest = prompt_digests(req.prompt, self.block_size)
        shared, tail_block = self.pages.match_prefix(full, tail_digest)
        return total - len(shared), (full, tail_digest, shared, tail_block)

    def _assert_writes_exclusive(self, append_row: np.ndarray) -> None:
        """Host-side write contract of ``Model.append_paged`` /
        ``paged_cache_write``: every block the scatter will actually
        write must be exclusively owned (refcount 1) — shared blocks are
        mapped read-only and must never be written."""
        for b in append_row:
            b = int(b)
            if b == NULL_BLOCK or b >= self.allocator.n_blocks:
                continue  # null page / drop sentinel: no live write
            assert self.allocator.refcount(b) == 1, (
                f"append would write block {b} with refcount "
                f"{self.allocator.refcount(b)} (shared blocks are "
                f"read-only)")

    def _map_paged_request(self, slot: int, req: ServeRequest, entry: Any,
                           plan: tuple) -> None:
        """Bind a slot's pages (shared prefix + private rest), publish its
        prompt digests, and scatter its prefill entry into the PRIVATE
        blocks only: shared prefix rows are already resident, so their
        entries go to the append drop sentinel and are never written."""
        rows = self._kv_rows_needed(req)
        full_digests, tail_digest, shared, tail_block = plan
        shared_all = shared + ([tail_block] if tail_block is not None
                               else [])
        if shared_all:
            self.pages.allocate_shared(slot, rows, shared_all,
                                       tail_shared=tail_block is not None)
            self.shared_block_hits += len(shared_all)
        else:
            self.pages.allocate(slot, rows)
        if self.prefix_sharing:
            self.pages.register_prefix(slot, full_digests, tail_digest)
        row = self.pages.row(slot, self.blocks_per_seq)
        self._tables[slot] = row
        self._pos[slot] = int(req.prompt.shape[0])
        self._state_dirty = True
        append_row = np.asarray(row, np.int32).copy()
        drop = self.allocator.n_blocks  # positive OOB -> scatter drops it
        append_row[:len(shared_all)] = drop  # resident prefix: read-only
        append_row[len(self.pages.blocks(slot)):] = drop  # padding rows
        self._assert_writes_exclusive(append_row)
        self.cache = self._append(self.cache, entry,
                                  jnp.asarray(append_row))

    def _admit(self) -> list[ServeRequest]:
        """Chunked admission: prefill queued requests one at a time into
        free slots and merge their caches into the live decode batch.

        Paged mode budgets FREE BLOCKS, not just free slots: the head of
        the queue is admitted only when the allocator can cover its whole
        lifetime (prompt + decode rows), so a mid-flight pool exhaustion
        is impossible and admission stays FIFO under block pressure.

        Fused mode never pulls the prefill argmax to the host here: the
        device token is scattered into the slot-token vector in-jit and
        queued for the pass's single ``sync_step`` pull.  The returned
        list holds the requests this admission completed (done at
        prefill) — in fused mode they are *counted* for fill accounting
        but only marked done at sync.
        """
        finished = []
        paged = self.batching == "paged"
        # A refill = joining a batch that was already decoding before this
        # step; cold-start co-admissions in the same pass don't count.
        had_live = self.n_active() > 0
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.queue:
                continue
            head = self.queue[0]
            plan = ([], None, [], None)
            if paged and head.max_new_tokens > 1:
                need, plan = self._plan_paged_admission(head)
                if not self.allocator.can_alloc(need):
                    break  # head-of-line waits for retiring blocks
            req = self.queue.popleft()
            logits, entry = self._prefill_one(req.prompt)
            if self.sampling is not None:
                # Same eager split in the fused and reference paths, so the
                # key stream (one split per admitted prefill, one per
                # round) is identical and sampled streams diff
                # bit-identical.  The split is async — no host pull.
                self._key_dev, sub = jax.random.split(self._key_dev)
                tok_dev = self._sample(logits, sub)
            else:
                tok_dev = self._greedy(logits)  # (1,) int32, stays on device
            if self.fused:
                done_at_prefill = (len(req.tokens_out) + 1
                                   >= req.max_new_tokens)
                self._pending_prefill.append(
                    (req, tok_dev, None if done_at_prefill else slot))
                if done_at_prefill:
                    finished.append(req)  # completed by sync_step
                    continue  # slot stays free for the next queued request
            else:
                self.sync_count += 1
                tok = int(np.asarray(tok_dev)[0])
                req.tokens_out.append(tok)
                self._mark_first_token()
                if len(req.tokens_out) >= req.max_new_tokens:
                    req.done = True
                    finished.append(req)
                    continue
            if self.cache is None:
                self.cache = self._init_cache()
            if had_live:
                self.refills += 1  # joined a live decode batch mid-flight
            if paged:
                # Sequences are keyed by SLOT, not req_id: slots are unique
                # within the instance and always released before reuse,
                # whereas req_ids from different engines can collide when
                # an evict re-routes queued requests across nodes.
                self._map_paged_request(slot, req, entry, plan)
            else:
                self.cache = self._merge(self.cache, entry, jnp.int32(slot))
            if self.speculate is not None:
                self._admit_draft(slot, req)
            self.slots[slot] = req
            if self.fused:
                self._slot_tok_dev = self._set_tok(
                    self._tok_dev(), jnp.int32(slot), tok_dev[0])
            else:
                self._slot_tok[slot] = tok  # type: ignore[possibly-undefined]
        return finished

    def _advance_slot(self, slot: int, tok: int) -> Optional[ServeRequest]:
        """Land one decode round's token on an occupied slot: append it,
        refresh the host mirrors (slot token; paged position, matching the
        in-jit ``pos + active``), and free the slot — paged blocks
        included — when the request finishes.  Returns the request iff
        this token completed it.  The single finish sequence shared by the
        fused sync and both host-argmax reference rounds."""
        req = self.slots[slot]
        req.tokens_out.append(tok)
        self._mark_first_token()
        self._slot_tok[slot] = tok
        if self.batching == "paged":
            self._pos[slot] += 1
        if len(req.tokens_out) >= req.max_new_tokens:
            req.done = True
            self.slots[slot] = None  # freed immediately for refill
            if self.batching == "paged":
                self._release_paged(slot)  # blocks reusable NOW
            return req
        return None

    def _sample_host(self, logits) -> np.ndarray:
        """Reference-path sampler: replay the fused round's in-jit
        ``split(key) -> sample`` sequence eagerly on the same key stream,
        so ``fused=False`` sampled tokens are bit-identical."""
        self._key_dev, sub = jax.random.split(self._key_dev)
        return np.asarray(self._sample(logits, sub), np.int32)

    def _decode_round_continuous(self) -> list[ServeRequest]:
        """Host-side argmax reference round (``fused=False``)."""
        logits, self.cache = self._decode(
            self.params, jnp.asarray(self._slot_tok), self.cache)
        self.sync_count += 1
        if self.sampling is not None:
            next_tok = self._sample_host(logits)
        else:
            next_tok = self._clip_tok(
                np.asarray(jnp.argmax(logits, axis=-1), np.int32))
        finished = []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue  # free slot decoded garbage; ignore it
            done = self._advance_slot(slot, int(next_tok[slot]))
            if done is not None:
                finished.append(done)
        return finished

    def _release_paged(self, slot: int) -> None:
        """Free a finished slot's blocks and park the slot on the null
        block so its garbage decode writes land in the trash page."""
        self.pages.release(slot)
        self._tables[slot] = NULL_BLOCK
        self._pos[slot] = 0
        self._state_dirty = True

    def _cow_round(self) -> None:
        """Resolve copy-on-write before a decode round's writes land.

        Every occupied slot's next append position is checked against the
        COW rule: a position inside a shared (refcount > 1) prompt-tail
        block pops that block's reserved spare, copies the device page
        (``Model.copy_block``), and re-points the slot's block table —
        the first divergent append then writes the private copy.  The
        closing assert is the host-side half of the paged write contract:
        after this pass, no dispatched write can touch a shared block.

        A speculating round writes a W = k+1 row window instead of one
        row, so COW resolves for EVERY block the window can touch
        (``pos .. pos+k``) — speculative rejection rollback is then a pure
        position trim: rejected rows land in exclusively-owned blocks,
        nothing is freed, and no shared/COW block is ever written.
        """
        span = 1 + (self.speculate.k if self.speculate is not None else 0)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            pos = int(self._pos[slot])
            first = pos // self.block_size
            last = (pos + span - 1) // self.block_size
            for idx in range(first, last + 1):
                block, moved = self.pages.writable_block(
                    slot, idx * self.block_size)
                if moved is not None:
                    old, new = moved
                    self.cache = self._copy_block(self.cache, jnp.int32(old),
                                                  jnp.int32(new))
                    self._tables[slot][idx] = new
                    self._state_dirty = True
                    self.cow_count += 1
                assert self.allocator.refcount(block) == 1

    def _decode_round_paged(self) -> list[ServeRequest]:
        """Host-side argmax reference round (``fused=False``)."""
        logits, self.cache = self._decode_paged(
            self.params, jnp.asarray(self._slot_tok), self.cache,
            jnp.asarray(self._tables), jnp.asarray(self._pos))
        self.sync_count += 1
        if self.sampling is not None:
            next_tok = self._sample_host(logits)
        else:
            next_tok = self._clip_tok(
                np.asarray(jnp.argmax(logits, axis=-1), np.int32))
        finished = []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue  # free slot decoded into the null block; ignore it
            done = self._advance_slot(slot, int(next_tok[slot]))
            if done is not None:
                finished.append(done)
        return finished

    # -- fused round: dispatch now, sync once per pass ----------------------

    def _dispatch_round(self) -> None:
        """Enqueue one fused decode round on the device — no host pull.

        The token vector, KV pool, and (paged) position vector are donated
        to the call and immediately replaced by the returned buffers; the
        results land in ``self._round`` for ``sync_step``.
        """
        active = [s for s, r in enumerate(self.slots) if r is not None]
        if self.speculate is not None:
            # Draft-k -> verify-1 in ONE donated jitted round: the k draft
            # steps, the W=k+1 verify forward, on-device rejection
            # sampling, and the per-slot position advance all ride the
            # pass's single sync (sync_step pulls the (B, k+1) window +
            # (B,) acceptance counts instead of a (B,) token vector).
            if self.batching == "paged":
                if self._state_dirty:
                    self._upload_paged_state()
                (tok, self.cache, self.dcache, self._pos_dev, out, n_emit,
                 self._key_dev) = self._spec_round(
                    self.params, self.draft_params, self._tok_dev(),
                    self.cache, self.dcache, self._tables_dev,
                    self._pos_dev, self._active_dev, self._key_dev)
            else:
                (tok, self.cache, self.dcache, out, n_emit,
                 self._key_dev) = self._spec_round(
                    self.params, self.draft_params, self._tok_dev(),
                    self.cache, self.dcache, self._key_dev)
            self._slot_tok_dev = tok
            self._round = (tok, active)
            self._round_spec = (out, n_emit)
            return
        if self.batching == "paged":
            if self._state_dirty:
                self._upload_paged_state()
            if self.sampling is not None:
                (tok, self.cache, self._pos_dev,
                 self._key_dev) = self._decode_paged_tok_s(
                    self.params, self._tok_dev(), self.cache,
                    self._tables_dev, self._pos_dev, self._active_dev,
                    self._key_dev)
            else:
                tok, self.cache, self._pos_dev = self._decode_paged_tok(
                    self.params, self._tok_dev(), self.cache,
                    self._tables_dev, self._pos_dev, self._active_dev)
        elif self.sampling is not None:
            tok, self.cache, self._key_dev = self._decode_tok_s(
                self.params, self._tok_dev(), self.cache, self._key_dev)
        else:
            tok, self.cache = self._decode_tok(
                self.params, self._tok_dev(), self.cache)
        self._slot_tok_dev = tok  # device-resident input of the next round
        self._round = (tok, active)

    @_on_device
    def dispatch_step(self) -> bool:
        """Dispatch one token-gated step WITHOUT any host synchronisation.

        Fused modes enqueue admission prefills and the decode round and
        return immediately (JAX async dispatch keeps the device busy while
        the caller dispatches sibling instances); the host-synchronous
        reference modes (``static``, ``fused=False``) execute the step in
        full and stash its completions.  Either way ``sync_step`` finishes
        the pass.  Returns False when paused (nothing dispatched).
        """
        if self.paused:
            # Mid-migration: admission and decode are frozen — the KV pool
            # is being gathered out from under the slots.
            return False
        self.steps += 1
        if self.batching == "static":
            if self.active:
                self.last_fill = sum(1 for r in self.active if not r.done)
                self._host_finished = self._decode_round_static()
            else:
                finished = self._admit_static()
                self.last_fill = len(self.active) or len(finished)
                self._host_finished = finished
            return True
        finished = self._admit()
        self.last_fill = self.n_active() + len(finished)
        if (self.batching == "paged" and self.prefix_sharing
                and self.n_active() > 0):
            # COW must resolve before this round's writes dispatch —
            # both the fused round below and the host-argmax reference.
            self._cow_round()
        if self.fused:
            if self.n_active() > 0:
                self._dispatch_round()
            return True
        if self.n_active() > 0:
            finished += (self._decode_round_paged()
                         if self.batching == "paged"
                         else self._decode_round_continuous())
        self._host_finished = finished
        return True

    def sync_step(self) -> list[ServeRequest]:
        """Complete the dispatched pass with ONE host synchronisation.

        Pulls every deferred device token (admission prefills + the decode
        round) in a single blocking point, appends them to their requests,
        refreshes the host mirrors (slot tokens, paged positions), and
        releases finished slots.  Returns the requests the pass completed.
        """
        if not self.fused:
            finished, self._host_finished = self._host_finished, []
            return finished
        if not self._pending_prefill and self._round is None:
            return []
        self.sync_count += 1  # the pass's single synchronisation point
        arrays = [t for _, t, _ in self._pending_prefill]
        if self._round is not None:
            arrays.append(self._round[0])
        if self._round_spec is not None:
            arrays.extend(self._round_spec)
        jax.block_until_ready(arrays)
        finished = []
        for req, tok_dev, slot in self._pending_prefill:
            tok = int(np.asarray(tok_dev)[0])
            req.tokens_out.append(tok)
            self._mark_first_token()
            if slot is None:  # whole request served by its prefill
                req.done = True
                finished.append(req)
            else:
                self._slot_tok[slot] = tok  # host mirror (migration seam)
        self._pending_prefill = []
        if self._round is not None and self._round_spec is not None:
            # Speculative round: land the accepted window per slot.  A slot
            # that reaches max_new mid-window is released immediately and
            # its surplus tokens dropped — the device position overshot,
            # but release resets the mirrors (paged: dirty re-upload;
            # continuous: the next merge overwrites the slot's pos).
            _, active = self._round
            out_dev, n_dev = self._round_spec
            self._round = self._round_spec = None
            out_np = np.asarray(out_dev)
            n_np = np.asarray(n_dev)
            for slot in active:
                n = int(n_np[slot])
                self.spec_proposed += self.speculate.k
                self.spec_accepted += n - 1
                done = None
                for t in out_np[slot, :n]:
                    done = self._advance_slot(slot, int(t))
                    if done is not None:
                        break
                if done is not None:
                    finished.append(done)
        elif self._round is not None:
            tok_dev, active = self._round
            self._round = None
            toks = np.asarray(tok_dev)
            for slot in active:
                done = self._advance_slot(slot, int(toks[slot]))
                if done is not None:
                    finished.append(done)
        return finished

    # -- migration seam (pause -> gather -> merge) --------------------------

    @_on_device
    def export_slot(self, slot: int) -> tuple[ServeRequest, Any, int]:
        """Gather one occupied slot's full decode state for migration:
        ``(request, batch-1 cache entry, last emitted token)``.

        Paged slots are re-gathered to the dense batch-1 layout
        (``Model.gather_pages``) so the entry is portable to any target
        instance, whatever physical blocks it has free.  Valid only
        between pump passes (every dispatched round synced): the host
        mirrors are refreshed by ``sync_step``.
        """
        req = self.slots[slot]
        if req is None:
            raise ValueError(f"slot {slot} of {self.inst_id} is empty")
        if self.speculate is not None:
            raise ValueError(
                f"{self.inst_id}: speculating slots cannot be exported — "
                f"the draft side cache does not travel (migrate gate)")
        if self.batching == "paged":
            entry = self.model.gather_pages(
                self.cache, jnp.asarray(self._tables[slot]),
                int(self._pos[slot]))
        else:
            entry = self.model.gather_slot(self.cache, jnp.int32(slot))
        return req, entry, int(self._slot_tok[slot])

    @_on_device
    def import_slot(self, slot: int, req: ServeRequest, entry: Any,
                    tok: int) -> None:
        """Merge an exported slot into this instance at ``slot`` — the
        exact inverse of :meth:`export_slot`, so a migrated request's
        remaining decode rounds produce bit-identical tokens."""
        if self.batching == "static":
            raise ValueError("static batches cannot absorb migrated slots")
        if self.slots[slot] is not None:
            raise ValueError(f"slot {slot} of {self.inst_id} is occupied")
        paged = self.batching == "paged"
        # The entry was gathered on the source node's device.
        entry = jax.device_put(entry, self.device)
        if self.cache is None:
            self.cache = self._init_cache()
        if paged:
            # Same worst-case reservation admission made on the source, so
            # the migrated request can never exhaust the pool mid-flight.
            # Prefix sharing re-establishes across a migrated cohort: FULL
            # prompt blocks match/register on the target (bit-identical —
            # cohort members shared the same physical pages on the
            # source), but the prompt-tail block stays private: the
            # gathered entry already holds decode rows past the prompt at
            # its tail offsets, which a later sharer must never see.
            rows = self._kv_rows_needed(req)
            full_digests: list = []
            if self.prefix_sharing:
                full_digests, _ = prompt_digests(req.prompt, self.block_size)
            shared, _ = self.pages.match_prefix(full_digests, None)
            if shared:
                self.pages.allocate_shared(slot, rows, shared)
                self.shared_block_hits += len(shared)
            else:
                self.pages.allocate(slot, rows)
            if self.prefix_sharing:
                self.pages.register_prefix(slot, full_digests, None)
            row = self.pages.row(slot, self.blocks_per_seq)
            self._tables[slot] = row
            self._pos[slot] = int(entry["pos"])
            self._state_dirty = True
            append_row = np.asarray(row, np.int32).copy()
            drop = self.allocator.n_blocks
            append_row[:len(shared)] = drop  # resident prefix: read-only
            append_row[len(self.pages.blocks(slot)):] = drop  # padding
            self._assert_writes_exclusive(append_row)
            self.cache = self._append(self.cache, entry,
                                      jnp.asarray(append_row))
        else:
            self.cache = self._merge(self.cache, entry, jnp.int32(slot))
        self.slots[slot] = req
        self._slot_tok[slot] = tok
        self._slot_tok_dev = None  # host mirror changed: re-upload lazily

    # -- static reference path ---------------------------------------------

    def _admit_static(self) -> list[ServeRequest]:
        batch = []
        while self.queue and len(batch) < self.max_batch:
            batch.append(self.queue.popleft())
        if not batch:
            return []
        prompts = np.stack([r.prompt for r in batch])
        logits, cache = self._prefill(self.params,
                                      jnp.asarray(prompts, jnp.int32))
        self.sync_count += 1
        next_tok = self._clip_tok(
            np.asarray(jnp.argmax(logits, axis=-1), np.int32))
        finished = []
        for r, t in zip(batch, next_tok):
            r.tokens_out.append(int(t))
            self._mark_first_token()
            if len(r.tokens_out) >= r.max_new_tokens:
                r.done = True
                finished.append(r)
        self.active = batch
        self.cache = cache
        self._retire_static_if_done()
        return finished

    def _decode_round_static(self) -> list[ServeRequest]:
        # Finished members keep their row in the batch (that is the point
        # of static batching) but stop accumulating tokens.
        toks = jnp.asarray([r.tokens_out[-1] for r in self.active], jnp.int32)
        logits, self.cache = self._decode(self.params, toks, self.cache)
        self.sync_count += 1
        next_tok = self._clip_tok(
            np.asarray(jnp.argmax(logits, axis=-1), np.int32))
        finished = []
        for r, t in zip(self.active, next_tok):
            if r.done:
                continue
            r.tokens_out.append(int(t))
            if len(r.tokens_out) >= r.max_new_tokens:
                r.done = True
                finished.append(r)
        self._retire_static_if_done()
        return finished

    def _retire_static_if_done(self) -> None:
        # Static-batch semantics: the batch retires together once ALL
        # members finish; no slot is re-filled mid-flight.
        if self.active and all(r.done for r in self.active):
            self.active = []
            self.cache = None

    # -- one token-gated step ----------------------------------------------

    def run_step(self) -> list[ServeRequest]:
        """One token-gated step; returns requests completed by it.

        ``dispatch_step`` + ``sync_step`` back to back — the synchronous
        protocol for callers outside the overlapping engine pump.
        """
        if not self.dispatch_step():
            return []
        return self.sync_step()


class ServingEngine:
    """One node: token scheduler + N weight-shared instances.

    ``device`` is the node's accelerator: its weights and KV pools live
    there (None keeps JAX's default device, as on a one-device host)."""

    def __init__(self, window: float = 0.2, idle_sleep_s: float = 0.001,
                 device: Optional[Any] = None):
        self.device = device
        self.scheduler = TokenScheduler(window=window)
        self.store = ModelStore()
        self.instances: dict[str, FunctionInstance] = {}
        self.recorders: dict[str, SLORecorder] = {}
        self.alive = True
        self._req_ids = itertools.count()
        self._inst_seq = itertools.count()
        self._t0 = time.perf_counter()
        # Quota-blocked idle lull: how long pump yields when a pass grants
        # nothing and the previous pass did no work.  0 disables the sleep
        # entirely (soak/chaos benchmarks run hot).
        self.idle_sleep_s = idle_sleep_s
        # Fault-injection hook: an artificial per-pass stall (seconds)
        # inside the timed dispatch region — the chaos harness's straggler
        # lever.  0 (default) is a no-op.
        self.pump_delay_s = 0.0
        # Gray-failure quarantine: routing and placement stop, occupants
        # keep draining through pump.  One-way, set by the frontend.
        self.quarantined = False
        # Pass-latency EWMAs for the health score: the fast one tracks the
        # current regime, the slow one the long-run baseline; their ratio
        # is the gray-failure signal (1.0 healthy, -> 0 degraded).
        self._lat_fast = 0.0
        self._lat_slow = 0.0
        # Per-instance expired-in-queue counts (telemetry).
        self._expired: dict[str, int] = {}
        # Scale-down hook: called with the instance id once a retired
        # instance has fully drained and released its resources (the
        # frontend uses it to release the MRA rectangle).
        self.on_instance_closed: Optional[Any] = None

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def deploy(self, fn: str, model: Model, params: Any, alloc: Alloc, *,
               n_instances: int = 1, max_batch: int = 4, max_len: int = 64,
               batching: str = "continuous", prefill_buckets: bool = True,
               block_size: int = 16, n_kv_blocks: Optional[int] = None,
               fused: bool = True, prefix_sharing: bool = True,
               sampling: Optional[SamplingConfig] = None,
               speculate: Optional[SpecConfig] = None,
               draft_params: Any = None,
               mesh: Optional[Any] = None) -> list[str]:
        if not self.alive:
            raise RuntimeError("cannot deploy to a failed node")
        if fn not in self.recorders:
            self.recorders[fn] = SLORecorder(fn=fn)
        # A sharded pod's weights live under their own store entry keyed by
        # the tensor degree, so shards=1 replicas of the same function keep
        # sharing the intact single-device tree.  shard_put is a no-op for
        # leaves the modelstore already uploaded to their owning devices.
        weights_key = fn if mesh is None else f"{fn}@tp{mesh.devices.size}"
        if not self.store.contains(weights_key):
            if mesh is not None:
                params = shard_put(params, model.param_names(), mesh,
                                   resolver=serve_pspec)
            else:
                params = jax.device_put(params, self.device)
            self.store.store(weights_key, params)
        draft_model = None
        draft_key = None
        if speculate is not None:
            from repro.models.model import build_model
            # Draft models are cached per function so their shared jit
            # executors (stored on the Model object) survive redeploys.
            cache = self.__dict__.setdefault("_draft_models", {})
            draft_model = cache.get(fn)
            if draft_model is None:
                draft_model = cache[fn] = build_model(speculate.draft_cfg)
            draft_key = f"{fn}#draft"
            if not self.store.contains(draft_key):
                if draft_params is None:
                    raise ValueError(
                        f"{fn}: speculate set but no draft weights staged "
                        f"(pass draft_params on the first deploy)")
                self.store.store(draft_key,
                                 jax.device_put(draft_params, self.device))
        ids = []
        for _ in range(n_instances):
            inst_id = f"{fn}/{next(self._inst_seq)}"
            inst = FunctionInstance(inst_id, model, self.store, weights_key,
                                    alloc,
                                    max_batch=max_batch, max_len=max_len,
                                    batching=batching,
                                    prefill_buckets=prefill_buckets,
                                    block_size=block_size,
                                    n_kv_blocks=n_kv_blocks, fused=fused,
                                    prefix_sharing=prefix_sharing,
                                    sampling=sampling, speculate=speculate,
                                    draft_model=draft_model,
                                    draft_key=draft_key, mesh=mesh,
                                    device=self.device)
            self.instances[inst_id] = inst
            self.scheduler.register(inst_id, alloc)
            ids.append(inst_id)
        return ids

    # -- scale-down (graceful drain) ---------------------------------------

    def retire(self, inst_id: str,
               strip_queue: bool = True) -> list[ServeRequest]:
        """Stop routing to an instance; returns its queued (not yet
        admitted) requests for the caller to re-route.  Occupied decode
        slots keep decoding under the token scheduler until they finish;
        the instance then closes (weights refcount released, scheduler
        deregistered) and ``on_instance_closed`` fires.

        ``strip_queue=False`` keeps queued requests with the instance — for
        the last replica of a function, which must drain its own queue
        before closing (there is nowhere to re-route)."""
        inst = self.instances[inst_id]
        inst.retired = True
        strays: list[ServeRequest] = []
        if strip_queue:
            strays = list(inst.queue)
            inst.queue.clear()
        if not inst.has_work():
            self._close(inst_id)
        return strays

    def _close(self, inst_id: str) -> None:
        inst = self.instances.pop(inst_id)
        self.scheduler.deregister(inst_id)
        inst.close()
        if self.on_instance_closed is not None:
            self.on_instance_closed(inst_id)

    # -- node failure (crash, no drain) ------------------------------------

    def fail(self) -> list[tuple[str, ServeRequest]]:
        """Simulate a node crash: every instance dies instantly — no drain,
        no ``on_instance_closed`` callbacks, weights and KV gone.

        Returns the stranded unfinished requests as ``(fn, request)``
        pairs, queued and slot-occupying alike.  A slot occupant's partial
        output is reset: its KV died with the node, so a surviving replica
        must re-execute it from the prompt (greedy decode reproduces the
        identical stream).
        """
        self.alive = False
        strays: list[tuple[str, ServeRequest]] = []
        for inst_id, inst in self.instances.items():
            fn = inst_id.split("/")[0]
            occupants = (inst.active if inst.batching == "static"
                         else inst.slots)
            for req in occupants:
                if req is None or req.done:
                    continue
                req.tokens_out = []  # KV lost: re-execute from scratch
                strays.append((fn, req))
            strays.extend((fn, req) for req in inst.queue)
        self.instances.clear()
        self.scheduler.pods.clear()  # crash: tokens die mid-hold
        self.store = ModelStore()    # node memory (weights, KV) is gone
        return strays

    def submit(self, fn: str, prompt: np.ndarray, max_new_tokens: int = 8,
               deadline: Optional[float] = None, tier: str = "best_effort",
               attempts: int = 0) -> ServeRequest:
        req = ServeRequest(req_id=next(self._req_ids), prompt=prompt,
                           max_new_tokens=max_new_tokens,
                           submitted_at=self.now(), deadline=deadline,
                           tier=tier, attempts=attempts)
        # Join-shortest-queue across the function's live instances (retired
        # ones are draining, paused ones are mid-migration: no new work).
        candidates = [v for k, v in self.instances.items()
                      if k.startswith(fn + "/") and not v.retired
                      and not v.paused]
        if not candidates:
            raise KeyError(f"function {fn} has no instances")
        inst = min(candidates, key=lambda i: i.load())
        # Reject requests that can never fit the instance's cache up front:
        # a dense cache would clamp writes past max_len (silent corruption),
        # a paged one would out-grow its block-table row mid-admission —
        # or, worse, head-of-line livelock on a pool smaller than the
        # request's lifetime (nothing in flight to ever free blocks).
        rows = (int(prompt.shape[0]) + max_new_tokens - 1
                + inst._spec_k(max_new_tokens))
        if rows > inst.max_len:
            raise ValueError(
                f"request needs {rows} KV rows (prompt "
                f"{int(prompt.shape[0])} + {max_new_tokens} new tokens + "
                f"{inst._spec_k(max_new_tokens)} speculation margin) > "
                f"max_len {inst.max_len} of {inst.inst_id}")
        if (inst.batching == "paged" and max_new_tokens > 1
                and blocks_needed(rows, inst.block_size)
                > inst.allocator.capacity):
            raise ValueError(
                f"request needs {blocks_needed(rows, inst.block_size)} KV "
                f"blocks > pool capacity {inst.allocator.capacity} of "
                f"{inst.inst_id}; raise n_kv_blocks or shorten the request")
        self.enqueue(inst, req)
        return req

    @staticmethod
    def enqueue(inst: FunctionInstance, req: ServeRequest) -> None:
        """Queue with the batch lane preempted: a non-batch request inserts
        ahead of parked batch-tier work; uniform tiers reduce to a plain
        FIFO append (the bit-identical legacy order)."""
        if req.tier != "batch":
            idx = next((i for i, r in enumerate(inst.queue)
                        if r.tier == "batch"), None)
            if idx is not None:
                inst.queue.insert(idx, req)
                return
        inst.queue.append(req)

    def has_work(self) -> bool:
        return any(i.has_work() for i in self.instances.values())

    def pump(self, budget_s: float = 1.0, *, overlap: bool = True) -> int:
        """Run token-gated dispatch until idle or budget exhausted.

        ``overlap=True`` (default) pipelines co-located instances: every
        granted instance's round is DISPATCHED first (JAX async dispatch
        queues the work and returns), then a second pass performs each
        instance's single host sync — so instance B's kernels execute
        while Python is still dispatching C and pulling A.
        ``overlap=False`` is the serialized reference (dispatch + sync one
        instance at a time) that ``benchmarks/decode_throughput.py``
        measures the overlap win against.
        """
        if not self.alive:
            return 0
        completed = 0
        deadline = time.perf_counter() + budget_s
        worked_last_pass = False
        while time.perf_counter() < deadline:
            self._expire_queued()
            any_work = False
            for inst_id, inst in list(self.instances.items()):
                if inst.has_work() and not inst.paused:
                    any_work = True
                    self.scheduler.request_token(inst_id, self.now())
            if not any_work:
                break
            granted = self.scheduler.dispatch(self.now())
            if not granted:
                # Quota-blocked, not idle: when the previous pass did real
                # work we are saturated and the next scheduling window is
                # imminent — spin instead of yielding mid-burst.  Only a
                # genuinely idle lull sleeps.
                if not worked_last_pass and self.idle_sleep_s > 0:
                    time.sleep(self.idle_sleep_s)
                worked_last_pass = False
                continue
            worked_last_pass = True
            t_prev = time.perf_counter()
            if self.pump_delay_s > 0:
                # Injected straggler stall: lands inside the timed region,
                # so it inflates the pass latency the health EWMAs see and
                # the Q_used the scheduler charges — a slow node looks
                # slow everywhere, exactly like the real gray failure.
                time.sleep(self.pump_delay_s)
            if overlap:
                # Only fused instances join the early dispatch pass: their
                # dispatch_step is a cheap async enqueue.  Host-synchronous
                # modes (static, fused=False) execute their whole round in
                # dispatch_step, so they run in the sync pass where their
                # compute is timed against their own Q_used, not the
                # first-synced sibling's.
                for token in granted:
                    inst = self.instances[token.pod_id]
                    if inst.fused:
                        inst.dispatch_step()
            # Sync pass: each instance's elapsed is the wall-clock delta to
            # its sync point — the clock starts BEFORE the dispatch pass,
            # so the full pass wall time (host dispatch overhead included,
            # exactly what the serialized path charged) is apportioned
            # across the overlapped instances without double-charging
            # Q_used; the first-synced instance absorbs the (cheap,
            # enqueue-only) dispatch leg.
            for token in granted:
                inst = self.instances[token.pod_id]
                if not overlap or not inst.fused:
                    inst.dispatch_step()
                finished = inst.sync_step()
                t_now = time.perf_counter()
                elapsed = t_now - t_prev
                t_prev = t_now
                self._observe_pass(elapsed)
                # Drained occupancy scales with slot fill: an underfilled
                # decode round cannot saturate the instance's SM share.
                occ = token.occ * min(inst.last_fill / inst.max_batch, 1.0)
                self.scheduler.complete(token.pod_id, elapsed, self.now(),
                                        occ=occ)
                fn = token.pod_id.split("/")[0]
                for r in finished:
                    r.finished_at = self.now()
                    met = (None if r.deadline is None
                           else r.finished_at <= r.deadline)
                    self.recorders[fn].record(r.finished_at - r.submitted_at,
                                              r.finished_at,
                                              deadline_met=met)
                    completed += 1
                if inst.retired and not inst.has_work():
                    self._close(token.pod_id)  # drained: release resources
        return completed

    def _expire_queued(self) -> None:
        """Drop queued non-guaranteed requests whose deadline has passed
        (typed outcome ``"expired"``) before spending a decode slot on
        them.  A no-op while every queued request is deadline-free."""
        now = self.now()
        for inst_id, inst in self.instances.items():
            if not inst.queue:
                continue
            kept, dropped = [], []
            for r in inst.queue:
                if (r.deadline is not None and r.tier != "guaranteed"
                        and now > r.deadline):
                    dropped.append(r)
                else:
                    kept.append(r)
            if not dropped:
                continue
            fn = inst_id.split("/")[0]
            for r in dropped:
                r.done = True
                r.outcome = "expired"
                r.finished_at = now
                self._expired[inst_id] = self._expired.get(inst_id, 0) + 1
                if fn in self.recorders:
                    self.recorders[fn].record_expired()
            inst.queue.clear()
            inst.queue.extend(kept)

    def _observe_pass(self, elapsed: float) -> None:
        """Feed one pump-pass latency into the fast/slow EWMAs."""
        if self._lat_slow == 0.0:
            self._lat_fast = self._lat_slow = elapsed
            return
        self._lat_fast = 0.6 * self._lat_fast + 0.4 * elapsed
        self._lat_slow = 0.98 * self._lat_slow + 0.02 * elapsed

    def health(self) -> float:
        """Node health score in (0, 1]: the slow/fast pass-latency EWMA
        ratio.  1.0 while pass latency tracks its long-run baseline; a node
        whose recent passes run Nx slower scores ~1/N.  A dead node is 0."""
        if not self.alive:
            return 0.0
        if self._lat_fast <= self._lat_slow or self._lat_fast == 0.0:
            return 1.0
        return self._lat_slow / self._lat_fast

    def memory_bytes(self) -> int:
        return self.store.used_bytes()

    def kv_bytes_in_use(self) -> int:
        """Physical KV bytes live requests hold across this node."""
        return sum(i.kv_bytes_in_use() for i in self.instances.values())

    def dense_kv_reserved(self) -> int:
        """What dense slot pools would reserve for the same capacity."""
        return sum(i.dense_kv_reserved() for i in self.instances.values())

    def kv_bytes_saved(self) -> int:
        """Bytes prefix sharing is saving across this node's instances."""
        return sum(i.kv_bytes_saved() for i in self.instances.values())

    # -- hot-path telemetry -------------------------------------------------

    def sync_counts(self) -> dict[str, int]:
        """Per-instance host-synchronisation counts.  The fused hot path's
        budget is exactly ONE per instance per pump pass (prefill argmaxes
        and the decode round share it); the host-argmax reference spends
        one per admitted prompt plus one per round."""
        return {k: v.sync_count for k, v in self.instances.items()}

    def telemetry(self) -> dict[str, dict[str, int]]:
        """Hot-path counters per instance: steps, host syncs, (paged)
        device-state uploads — ``uploads << steps`` proves the block
        tables/positions stay device-resident between admission events —
        plus prefix-sharing hits and COW resolutions and the count of
        queued requests expired past their deadline."""
        return {k: {"steps": v.steps, "syncs": v.sync_count,
                    "uploads": v.uploads, "shared_hits": v.shared_block_hits,
                    "cow": v.cow_count, "spec_proposed": v.spec_proposed,
                    "spec_accepted": v.spec_accepted,
                    "expired": self._expired.get(k, 0)}
                for k, v in self.instances.items()}
