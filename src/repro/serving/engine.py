"""InstanceEngine: the real-execution serving instance.

One engine = one AcceLLM *instance* (paper: 4 accelerators under TP; here:
a JAX device set / submesh, or a single CPU device in the examples). It owns

  * the model params (full replica per instance — AcceLLM §4.2),
  * a slot-based continuous batch: fixed ``num_slots`` requests in flight,
  * a :class:`repro.kvstore.PagedStore` holding the serving state (KV
    caches / SSM states) for all slots behind a block-table ledger,
  * per-slot clocks (lengths) — decode runs with per-request ``t``.

Redundancy primitives used by the AcceLLM core:
  export_slot / import_slot    — whole per-request state; ``export_stream``
                                 yields it as per-layer chunks (prefill-time
                                 KV streaming; on a TPU mesh this is the
                                 per-layer ppermute described in DESIGN.md §3)
  sync_replica_from            — the per-decode-step mirror update: ONLY the
                                 new KV lines since the replica's synced
                                 mark move (constant-size state copy for
                                 SSMs) — O(delta), not O(kv_capacity)

All line/byte accounting (primaries AND replicas) flows through the
store's ledger, the same arithmetic the simulator's ``SimStore`` runs.

The engine never batches prefill with decode (AcceLLM §4.2.3: vLLM modified
so prefill and decode are never co-scheduled on one instance).
"""
from __future__ import annotations

import collections
import contextlib
import functools
from typing import (TYPE_CHECKING, Dict, Iterable, List, Mapping, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kvstore import PagedStore
from repro.models import (decode_multi, decode_step, init_state, prefill,
                          prefill_batched, prefill_chunk)
from repro.models.state import state_bytes
from repro.serving.request import Phase, Request
from repro.serving.sampling import decode_keys, sample_slots

if TYPE_CHECKING:  # runtime import is lazy: stepplan -> ... -> engine cycle
    from repro.stepplan import (DecodePlan, PrefillItem,  # noqa: F401
                                PrefillPlan)


class InstanceEngine:
    def __init__(self, cfg: ModelConfig, params, num_slots: int,
                 kv_capacity: int, instance_id: int = 0,
                 temperature: float = 0.0, eos_token: Optional[int] = None,
                 seed: int = 0, block_lines: Optional[int] = None,
                 paged_decode: Optional[bool] = None,
                 prefix_cache: bool = False,
                 prefix_cache_blocks: Optional[int] = None,
                 mesh=None):
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.kv_capacity = kv_capacity
        self.instance_id = instance_id
        self.temperature = temperature
        self.eos_token = eos_token
        self.store = PagedStore(cfg, num_slots, kv_capacity,
                                block_lines=block_lines)
        #: mesh slice backing this instance (repro.meshserve.MeshSlice):
        #: params and the KV pool are committed to its devices and every
        #: model dispatch runs under its sharding context — tensor
        #: parallelism within the instance, with redundancy traffic to
        #: other instances riding the cross-slice collectives.  ``None``
        #: keeps the seed single-device behavior.
        self.mesh = mesh
        if mesh is not None:
            from repro.meshserve import shard_params, shard_store
            self.params = shard_params(cfg, params, mesh)
            shard_store(self.store, mesh)
            self._model_axis = mesh.model_axis_for(cfg)
        self.lengths = np.zeros((num_slots,), np.int32)
        self.last_tokens = np.zeros((num_slots,), np.int32)
        self.slot_req: Dict[int, Request] = {}
        # replica slots: requests whose primary lives on the paired instance
        self.replica_of: Dict[int, Tuple[int, int]] = {}  # slot -> (inst, slot)
        # slots mid-chunked-prefill: occupied, but not yet decoding
        self.prefilling: Dict[int, Request] = {}
        self._key = jax.random.PRNGKey(seed + instance_id)
        #: device->host materializations on the decode path (the sync the
        #: fused scan amortizes: 1/token dense-per-step vs 1/plan fused)
        self.host_syncs = 0
        #: model programs dispatched, by jit and argument shape key —
        #: ("prefill", prompt_len, window), ("prefill_batched", rows,
        #: bucket), ("prefill_chunk", chunk_len, history), ("decode",),
        #: ("decode_multi", rows, blocks, steps) — counting the requests
        #: each carried: the record a check re-lowers the served programs
        #: from
        self.programs: Dict[tuple, int] = collections.Counter()
        #: uploaded decode block tables, keyed by (resident rids, block
        #: bucket) — slot-affine tables are growth-stable, so they only
        #: rebuild when batch membership or the bucket changes
        self._tables_cache: Optional[Tuple[tuple, jnp.ndarray]] = None
        self._jit_decode = jax.jit(
            functools.partial(decode_step, cfg), donate_argnums=(2,))
        self._jit_prefill = jax.jit(functools.partial(prefill, cfg))
        # bucketed batched prefill: one compile per (batch, bucket) shape
        self._jit_prefill_batched = jax.jit(
            functools.partial(prefill_batched, cfg))
        # chunk resume: `history` is the static cursor
        self._jit_prefill_chunk = jax.jit(
            functools.partial(prefill_chunk, cfg),
            static_argnames=("history",))
        # the padded batched path and chunk resume need every KV row to
        # be maskable by the decode clock — attention-only decoder stacks
        self._attn_only = (all(b == "attn" for b in cfg.block_pattern)
                           and not cfg.is_encoder_decoder
                           and cfg.frontend is None)
        if paged_decode is None:
            paged_decode = self.supports_paged_decode
        #: decode through the block-table gather kernel with the batch
        #: compacted to active primary slots (vs the dense full-window,
        #: full-batch oracle path)
        self.use_paged_decode = paged_decode and self.supports_paged_decode
        #: radix prefix cache over the store's ledger (suffix-only
        #: prefill rides the chunk path, so attention-only stacks only)
        self.prefix_cache = None
        if prefix_cache and self.supports_chunked_prefill:
            from repro.prefixcache import PrefixCache
            if prefix_cache_blocks is None:
                prefix_cache_blocks = (num_slots
                                       * self.store.line_blocks_per_slot) // 2
            self.prefix_cache = PrefixCache(
                self.store.ledger, capacity_blocks=prefix_cache_blocks)
        #: pinned hit runs awaiting their prefill's first chunk
        self._hit_runs: Dict[int, List[int]] = {}
        # fused multi-step decode: compiles per (batch, table, steps)
        # shape; eos/temperature are baked in as compile-time constants
        self._jit_decode_multi = jax.jit(
            functools.partial(
                decode_multi, cfg, block_lines=self.store.block_lines,
                temperature=temperature,
                eos_token=-1 if eos_token is None else eos_token),
            donate_argnums=(2,))

    @property
    def supports_chunked_prefill(self) -> bool:
        """Whether this engine can resume a prompt mid-chunk (recurrent
        state continuation across chunks is not implemented)."""
        return self._attn_only

    @property
    def supports_paged_decode(self) -> bool:
        """Paged decode gathers per-head K/V line blocks: attention-only
        decoder stacks with GQA attention (MLA decodes through the
        absorbed latent path; recurrent blocks carry no line-indexed
        cache to gather)."""
        return self._attn_only and self.cfg.attention_kind == "gqa"

    @property
    def state(self):
        return self.store.state

    @state.setter
    def state(self, value):
        self.store.state = value

    def _mesh_ctx(self):
        """Sharding context for this engine's model dispatches.  On a
        mesh slice the trace-time constraints bind to the slice's mesh
        (no batch axis — a serving batch stays whole per instance, only
        heads split); single-device engines get a no-op.  The jits are
        per-engine, so each traces exactly once under its own slice."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro import sharding
        return sharding.use_mesh(self.mesh.mesh, batch_axes=(),
                                 model_axis=self._model_axis)

    # -- capacity ------------------------------------------------------------
    def free_slots(self) -> List[int]:
        """Slots usable for a fresh admission: unoccupied AND with an
        allocatable own block region.  A released slot whose blocks live
        on under the prefix cache still counts (``_clean_slot`` evicts
        those entries on take); one kept alive by another table's shared
        reference does not — its rows are live data."""
        used = (set(self.slot_req) | set(self.replica_of)
                | set(self.prefilling))
        out = []
        cached = (set(self.prefix_cache.index.blocks())
                  if self.prefix_cache is not None else set())
        pinned = (self.prefix_cache.pinned()
                  if self.prefix_cache is not None else set())
        for s in range(self.num_slots):
            if s in used:
                continue
            held = self.store.slot_used_blocks(s)
            if all(self.store.ledger.refcount(b) == 1 and b in cached
                   and b not in pinned for b in held):
                out.append(s)
        return out

    def active_slots(self) -> List[int]:
        return sorted(self.slot_req)

    @property
    def batch_size(self) -> int:
        return len(self.slot_req)

    def primary_kv_tokens(self) -> int:
        return int(sum(self.store.lines(r.rid)
                       for r in self.slot_req.values()))

    def replica_kv_tokens(self) -> int:
        return int(sum(self.store.lines(self.store.slot_rid[s])
                       for s in self.replica_of))

    def total_kv_tokens(self) -> int:
        """KV lines resident on this instance — primaries AND replicas
        (replica bytes are real HBM; the balancer must see them)."""
        return self.primary_kv_tokens() + self.replica_kv_tokens()

    def state_bytes(self) -> int:
        """Physical bytes of the allocated state arrays."""
        return state_bytes(self.store.state)

    def used_bytes(self) -> float:
        """Ledger bytes of resident requests (primaries + replicas)."""
        return self.store.used_bytes()

    def free_blocks(self) -> int:
        return self.store.free_blocks()

    def _rid_at(self, slot: int) -> int:
        return self.store.slot_rid[slot]

    # -- prefix cache ----------------------------------------------------------
    def _prefix_key(self, req: Request) -> List[int]:
        """Radix key for ``req``'s shareable prompt head: real token ids,
        trimmed to the block-aligned usable hit length.  Empty when the
        request declares no sharing (the index only ever sees declared
        prefixes, exactly like the token-free simulator's)."""
        if (self.prefix_cache is None or req.prefix_id is None
                or req.extra or req.prompt_tokens is None):
            return []
        from repro.prefixcache import aligned_hit_lines
        n = aligned_hit_lines(req.prefix_len, req.prompt_len,
                              self.store.block_lines)
        if n <= 0:
            return []
        return [int(t) for t in np.asarray(req.prompt_tokens)[0, :n]]

    def prefix_stamp(self, req: Request) -> int:
        """Consult the index once, when the prefill is first scheduled:
        stamps ``req.prefix_hit`` (the planner prices the suffix from it)
        and pins the hit run so eviction cannot release it before the
        first chunk adopts it.  Idempotent across re-planning."""
        if req.prefix_hit is not None:
            return req.prefix_hit
        key = self._prefix_key(req)
        blocks = (self.prefix_cache.lookup_pin(req.rid, key)
                  if key else [])
        if blocks:
            self._hit_runs[req.rid] = blocks
        req.prefix_hit = len(blocks) * self.store.block_lines
        return req.prefix_hit

    def prefix_abandon(self, req: Request):
        """The stamped prefill will not run here after all (requeued or
        its instance died): drop the pin and the stamp so the next
        placement consults its own instance's cache."""
        self._hit_runs.pop(req.rid, None)
        if self.prefix_cache is not None:
            self.prefix_cache.unpin(req.rid)
        req.prefix_hit = None

    def _prefix_insert(self, req: Request):
        """Index the just-prefilled request's shareable head (its table's
        leading blocks gain a cache reference)."""
        key = self._prefix_key(req)
        if not key:
            return
        k = len(key) // self.store.block_lines
        self.prefix_cache.insert(key, self.store.ledger.tables[req.rid][:k])

    def _clean_slot(self, slot: int) -> int:
        """Make ``slot``'s own block region allocatable, evicting cache
        entries that are its only remaining referents."""
        used = self.store.slot_used_blocks(slot)
        if used:
            assert self.prefix_cache is not None, \
                f"slot {slot} region held with no cache to evict"
            self.prefix_cache.evict_obstructing(set(used))
            assert not self.store.slot_used_blocks(slot), \
                f"slot {slot} region still referenced after cache purge"
        return slot

    # -- prefill --------------------------------------------------------------
    def prefill_request(self, req: Request, extra: Optional[dict] = None
                        ) -> int:
        """Run the prompt through the model into a free slot; returns the
        slot.  Thin wrapper over :meth:`prefill_batch` with a one-item
        plan (scratch sized to the padded bucket, not kv_capacity)."""
        from repro.stepplan import PrefillItem, PrefillPlan, bucket_len
        item = PrefillItem(req.rid, req.prompt_len, 0, req.prompt_len,
                           req=req)
        plan = PrefillPlan(self.instance_id, (item,),
                           bucket_len(req.prompt_len, cap=self.kv_capacity))
        done = self.prefill_batch(plan, extras={req.rid: extra})
        return done[req.rid]

    def prefill_batch(self, plan: PrefillPlan,
                      extras: Optional[Mapping[int, Optional[dict]]] = None
                      ) -> Dict[int, int]:
        """Execute one prefill step plan; returns {rid: slot} for every
        request whose prefill *completed* this iteration.

        Whole-prompt items on attention-only stacks run as ONE jitted
        call, right-padded to ``plan.bucket_len`` (batch padded to a
        power of two as well) — the jit cache is keyed by bucket shapes,
        so a stream of arbitrary prompt lengths compiles O(log max_len)
        kernels instead of one per length.  Scratch state is allocated
        at the bucket length, not ``kv_capacity``.  Items that cannot
        pad (modality extras, recurrent blocks, enc-dec, prompts beyond
        the bucket) run the unpadded single-prompt path with
        bucket-sized scratch.  Chunk items (``start > 0`` or partial
        ``end``) resume through the KV ledger cursor."""
        extras = extras or {}
        completed: Dict[int, int] = {}
        padded: List[PrefillItem] = []
        for it in plan.items:
            extra = extras.get(it.rid)
            if extra is None and getattr(it.req, "extra", None):
                extra = it.req.extra
            if not (it.start == 0 and it.completes):
                if (not self._attn_only or extra) and it.start == 0:
                    # can't resume this prompt mid-chunk here: degrade
                    # to one whole-prompt call rather than crash (the
                    # caller sees it completed ahead of its cursor)
                    completed[it.rid] = self._prefill_single(it.req, extra)
                    continue
                slot = self._prefill_chunk_item(it, extra)
                if slot is not None:
                    completed[it.rid] = slot
            elif (self._attn_only and not extra
                    and it.prompt_len <= min(plan.bucket_len,
                                             self.kv_capacity)):
                padded.append(it)
            else:
                completed[it.rid] = self._prefill_single(it.req, extra)
        if padded:
            # plan buckets are backend-agnostic; scratch is clamped to
            # this engine's cache window at execution time
            completed.update(self._prefill_padded(
                padded, min(plan.bucket_len, self.kv_capacity)))
        return completed

    def _take_slot(self) -> int:
        free = self.free_slots()
        assert free, f"instance {self.instance_id} has no free slot"
        return self._clean_slot(free[0])

    def _finish_prefill(self, req: Request, slot: int, tok: int,
                        ledgered: bool = False):
        self.lengths[slot] = req.prompt_len
        self.last_tokens[slot] = tok
        self.slot_req[slot] = req
        req.phase = Phase.DECODE
        req.generated += 1
        req.output_tokens.append(tok)
        # ledger: prompt lines + the reserved line for the sampled token
        if ledgered:
            self.store.set_lines(req.rid, req.total_len)
        else:
            self.store.alloc(req.rid, slot, lines=req.total_len)
        if self.prefix_cache is not None:
            self._prefix_insert(req)

    def _prefill_single(self, req: Request, extra: Optional[dict]) -> int:
        """Unpadded single-prompt path (modality extras, recurrent or
        enc-dec stacks); scratch sized to the prompt's bucket when the
        batch is token-only, else the full window (prefix tokens /
        encoder memory need the room)."""
        slot = self._take_slot()
        from repro.stepplan import bucket_len
        batch = {"tokens": req.prompt_tokens}
        if extra:
            batch.update(extra)
        window = (bucket_len(req.prompt_len, cap=self.kv_capacity)
                  if self._attn_only and not extra else self.kv_capacity)
        self.programs["prefill", req.prompt_len, window] += 1
        fresh = init_state(self.cfg, 1, window)
        with self._mesh_ctx():
            logits, fresh = self._jit_prefill(self.params, batch, fresh)
        self._key, sub = jax.random.split(self._key)
        tok = int(sample_slots(logits, sub, jnp.asarray([slot]),
                               self.temperature)[0])
        self.store.merge_slot_rows(slot, fresh, 0, window)
        self._finish_prefill(req, slot, tok)
        return slot

    def _prefill_padded(self, items: List[PrefillItem], bucket: int
                        ) -> Dict[int, int]:
        """Batched bucketed prefill: all items in one jitted call."""
        from repro.stepplan import bucket_len
        slots = self.free_slots()
        assert len(slots) >= len(items), \
            f"instance {self.instance_id}: {len(items)} prefills, " \
            f"{len(slots)} free slots"
        for s in slots[:len(items)]:
            self._clean_slot(s)
        B = len(items)
        Bp = bucket_len(B, floor=1)
        toks = np.zeros((Bp, bucket), np.int32)
        lens = np.ones((Bp,), np.int32)
        for i, it in enumerate(items):
            toks[i, :it.prompt_len] = np.asarray(it.req.prompt_tokens)[0]
            lens[i] = it.prompt_len
        fresh = init_state(self.cfg, Bp, bucket)
        self.programs["prefill_batched", Bp, bucket] += B
        with self._mesh_ctx():
            logits, fresh = self._jit_prefill_batched(
                self.params, jnp.asarray(toks), fresh, jnp.asarray(lens))
        self._key, sub = jax.random.split(self._key)
        # pad rows fold in an unused sentinel slot; their draws are
        # discarded and never perturb a real slot's stream
        row_slots = np.full((Bp,), self.num_slots, np.int32)
        row_slots[:B] = slots[:B]
        next_toks = np.asarray(sample_slots(logits, sub,
                                            jnp.asarray(row_slots),
                                            self.temperature))
        out: Dict[int, int] = {}
        for i, it in enumerate(items):
            slot = slots[i]
            self.store.merge_slot_rows(slot, fresh, 0, bucket, src_slot=i)
            self._finish_prefill(it.req, slot, int(next_toks[i]))
            out[it.rid] = slot
        return out

    def _prefill_chunk_item(self, it: PrefillItem, extra: Optional[dict]
                            ) -> Optional[int]:
        """One resumable chunk of a prompt; returns the slot when the
        final chunk completes the prefill, else None."""
        req = it.req
        if not self._attn_only or extra:
            raise NotImplementedError(
                "chunked prefill needs an attention-only decoder stack "
                "(recurrent state continuation across chunks is not "
                "implemented) and a token-only batch")
        if req.prompt_len > self.kv_capacity:
            raise NotImplementedError(
                f"chunked prefill of a {req.prompt_len}-token prompt "
                f"would wrap the {self.kv_capacity}-line cache window")
        if req.rid not in self.store.rid_slot:
            # first chunk: admit the request.  A prefix-cache hit adopts
            # the cached run as the table head (ledger: suffix blocks
            # only) and gathers the hit rows into this slot's window
            # once — the chunk below then resumes *past* the hit, never
            # recomputing it.
            slot = self._take_slot()
            self.prefilling[slot] = req
            req.phase = Phase.PREFILL
            hit = int(req.prefix_hit or 0)
            run = self._hit_runs.pop(req.rid, None)
            if hit:
                assert run is not None, \
                    f"rid {req.rid}: stamped hit {hit} lost its run"
                assert it.start == hit, (it.start, hit)
                self.store.alloc(req.rid, slot, lines=hit, shared=run)
                self.store.copy_prefix(run, slot, hit)
                self.prefix_cache.unpin(req.rid)
                self.lengths[slot] = hit
            else:
                self.store.alloc(req.rid, slot, lines=0)
        else:
            slot = self.store.rid_slot[req.rid]
            assert self.prefilling.get(slot) is req
        toks = req.prompt_tokens[:, it.start:it.end]
        self.programs["prefill_chunk", it.end - it.start, it.start] += 1
        sub = self.store.extract_slot(slot)
        with self._mesh_ctx():
            logits, sub = self._jit_prefill_chunk(self.params, toks, sub,
                                                  history=it.start)
        self.store.merge_slot_rows(slot, sub, it.start, it.end)
        if not it.completes:
            # cursor over the KV ledger: lines materialized so far.  The
            # decode step this iteration writes a garbage row at the
            # cursor for this slot; the next chunk overwrites it.
            self.store.set_lines(req.rid, it.end)
            self.lengths[slot] = it.end
            return None
        del self.prefilling[slot]
        self._key, sub_key = jax.random.split(self._key)
        tok = int(sample_slots(logits, sub_key, jnp.asarray([slot]),
                               self.temperature)[0])
        self._finish_prefill(req, slot, tok, ledgered=True)
        return slot

    # -- decode ----------------------------------------------------------------
    def decode(self) -> Dict[int, int]:
        """One decode iteration over the active slots; returns
        slot->token.  Paged engines run the compacted single-step fused
        path; others the dense full-batch oracle."""
        if not self.slot_req:
            # a release mid-iteration can empty the batch: never pay a
            # jitted full-batch dispatch to generate nothing
            return {}
        if self.use_paged_decode:
            return {slot: toks[0]
                    for slot, toks in self.decode_multi(steps=1).items()}
        tokens = jnp.asarray(self.last_tokens)[:, None]
        t = jnp.asarray(self.lengths)
        self.programs["decode",] += len(self.slot_req)
        with self._mesh_ctx():
            logits, self.store.state = self._jit_decode(
                self.params, tokens, self.store.state, t)
        self._key, sub = jax.random.split(self._key)
        # per-slot keys (fold_in by slot index == row index here) keep
        # sampled tokens invariant to batch compaction on the paged path
        next_tokens = np.asarray(sample_slots(
            logits, sub, jnp.arange(self.num_slots), self.temperature))
        self.host_syncs += 1
        out = {}
        for slot, req in list(self.slot_req.items()):
            # rows of free/replica slots hold garbage logits: sampled
            # tokens are read ONLY at active primary slots (this loop),
            # and those must be real rows of the batch
            assert 0 <= slot < next_tokens.shape[0]
            tok = int(next_tokens[slot])
            self.lengths[slot] += 1
            self.last_tokens[slot] = tok
            req.generated += 1
            req.output_tokens.append(tok)
            self.store.append_line(req.rid)
            out[slot] = tok
            if req.done or (self.eos_token is not None
                            and tok == self.eos_token):
                req.phase = Phase.DONE
                self.release(slot)
        return out

    def decode_multi(self, plan: Optional["DecodePlan"] = None,
                     steps: Optional[int] = None) -> Dict[int, List[int]]:
        """Execute a (possibly fused) decode plan: ``steps`` decode
        iterations as ONE jitted ``lax.scan`` over the compacted active
        batch, with on-device sampling and EOS short-circuiting — one
        dispatch and one host transfer per plan instead of per token.
        Returns {slot: [tokens]} (a dead row stops contributing).

        Engines without paged-decode support degrade to sequential
        single-step calls (same tokens, per-step host syncs)."""
        if steps is None:
            steps = max(1, plan.steps) if plan is not None else 1
        if not self.slot_req:
            return {}
        if not self.use_paged_decode:
            out: Dict[int, List[int]] = {}
            for _ in range(steps):
                if not self.slot_req:
                    break
                for slot, tok in self.decode().items():
                    out.setdefault(slot, []).append(tok)
            return out
        slots = self.active_slots()
        reqs = [self.slot_req[s] for s in slots]
        budget = np.asarray([r.max_new_tokens - r.generated for r in reqs],
                            np.int32)
        # never scan past the last live row's budget: trailing steps
        # would only re-freeze dead rows
        steps = max(1, min(steps, int(budget.max())))
        t0 = self.lengths[slots].astype(np.int32)
        # tables cover the lines the scan can reach; padded to a
        # power-of-two block count so compiles stay O(log window)
        from repro.stepplan import bucket_len
        need = -(-min(int(t0.max()) + steps, self.kv_capacity)
                 // self.store.block_lines)
        blocks = bucket_len(need, floor=1,
                            cap=self.store.line_blocks_per_slot)
        # the slot-affine tables are growth-stable: reuse the uploaded
        # array until batch membership — (rid, slot) pairs, since a
        # request can leave and re-enter at a different slot — or the
        # block bucket changes (rebuilding per token would tax the
        # default steps=1 path)
        cache_key = (tuple(slots), tuple(r.rid for r in reqs), blocks)
        if self._tables_cache is None or self._tables_cache[0] != cache_key:
            self._tables_cache = (cache_key, jnp.asarray(
                self.store.decode_block_tables([r.rid for r in reqs],
                                               blocks)))
        tables = self._tables_cache[1]
        key_chain, keys = decode_keys(self._key, steps)
        self.programs["decode_multi", len(slots), blocks, steps] += len(slots)
        with self._mesh_ctx():
            toks_all, self.store.state, emitted = self._jit_decode_multi(
                self.params, jnp.asarray(self.last_tokens[slots])[:, None],
                self.store.state, jnp.asarray(t0), jnp.asarray(slots),
                tables, jnp.asarray(budget), keys)
        toks_np = np.asarray(toks_all)
        emitted = np.asarray(emitted)
        self.host_syncs += 1
        # consume key splits only for iterations that actually ran (EOS
        # can empty the batch early; sequential decode would have
        # stopped splitting there) — fused and per-step paths agree on
        # the key state the NEXT request samples under
        self._key = key_chain[int(emitted.max())]
        out = {}
        for i, slot in enumerate(slots):
            req = reqs[i]
            n = int(emitted[i])
            if n == 0:
                continue
            toks = [int(x) for x in toks_np[:n, i]]
            out[slot] = toks
            req.generated += n
            req.output_tokens.extend(toks)
            self.store.append_line(req.rid, n)
            self.lengths[slot] += n
            self.last_tokens[slot] = toks[-1]
            if req.done or (self.eos_token is not None
                            and toks[-1] == self.eos_token):
                req.phase = Phase.DONE
                self.release(slot)
        return out

    # -- slot management --------------------------------------------------------
    def release(self, slot: int) -> int:
        """Free the slot; returns the number of blocks returned to the
        pool."""
        self.slot_req.pop(slot, None)
        self.replica_of.pop(slot, None)
        self.prefilling.pop(slot, None)
        freed = self.store.free_slot(slot)
        self.lengths[slot] = 0
        # a stale token here would leak into a later occupant's first
        # decode if any path ever read before writing; clear with lengths
        self.last_tokens[slot] = 0
        return freed

    # -- redundancy primitives ---------------------------------------------------
    def export_slot(self, slot: int):
        """Per-request state + clocks, for replication to the pair
        partner (whole-state form; :meth:`export_stream` is the
        per-layer-chunk form a real mesh overlaps with prefill)."""
        return (self.store.extract_slot(slot), int(self.lengths[slot]),
                int(self.last_tokens[slot]), self.store.lines(self._rid_at(slot)))

    def export_stream(self, slot: int):
        """Per-layer streamed export: ``(chunk_iter, length, last_token,
        lines)``."""
        return (self.store.stream_slot(slot), int(self.lengths[slot]),
                int(self.last_tokens[slot]),
                self.store.lines(self._rid_at(slot)))

    def import_slot(self, slot: int, exported, req: Request,
                    as_replica_of: Optional[Tuple[int, int]] = None):
        sub_state, length, last_tok, lines = exported
        self._clean_slot(slot)
        self.store.alloc(req.rid, slot, lines=lines)
        self.store.merge_slot(slot, sub_state)
        self._install(slot, length, last_tok, req, as_replica_of)

    def import_stream(self, slot: int, chunks: Iterable, length: int,
                      last_tok: int, lines: int, req: Request,
                      as_replica_of: Optional[Tuple[int, int]] = None):
        """Install a per-layer streamed export chunk by chunk.  When this
        instance's prefix cache already holds the request's prompt head,
        the new table adopts those blocks — a shared-prefix replica costs
        only its unique suffix in pool blocks (the redundancy interplay
        the paper's HBM argument rides on)."""
        self._clean_slot(slot)
        run = None
        if self.prefix_cache is not None:
            key = self._prefix_key(req)
            run = self.prefix_cache.peek_blocks(key) if key else None
        self.store.alloc(req.rid, slot, lines=lines, shared=run or None)
        for path, chunk in chunks:
            self.store.import_chunk(slot, path, chunk)
        self._install(slot, length, last_tok, req, as_replica_of)

    def _install(self, slot: int, length: int, last_tok: int, req: Request,
                 as_replica_of: Optional[Tuple[int, int]]):
        self.lengths[slot] = length
        self.last_tokens[slot] = last_tok
        if as_replica_of is not None:
            self.replica_of[slot] = as_replica_of
        else:
            self.slot_req[slot] = req

    def promote_replica(self, slot: int, req: Request):
        """Instant role-flip enabled by redundancy (AcceLLM §4.1.2): a
        replica slot becomes the primary with zero data movement."""
        assert slot in self.replica_of
        del self.replica_of[slot]
        self.slot_req[slot] = req
        self.store.mark_synced(req.rid)

    def demote_to_replica(self, slot: int, of: Tuple[int, int]):
        assert slot in self.slot_req
        rid = self.slot_req[slot].rid
        del self.slot_req[slot]
        self.replica_of[slot] = of
        # an ex-primary's copy is current by definition
        self.store.mark_synced(rid)

    def sync_replica_from(self, src: "InstanceEngine", src_slot: int,
                          dst_slot: int, from_line: Optional[int] = None,
                          to_line: Optional[int] = None) -> float:
        """Mirror the partner's newly generated KV line(s) into our
        replica slot (AcceLLM §4.1.2 'newly computed KV cache lines are
        transferred back'): copies ONLY lines ``[from_line, to_line)``
        (default: our ledger's synced mark up to the primary's current
        lines) plus the constant-size recurrent states.  Returns the
        bytes moved — one KV line per decode step in steady state."""
        rid = src._rid_at(src_slot)
        if to_line is None:
            to_line = src.store.lines(rid)
        if from_line is None:
            from_line = self.store.synced_line(rid)
        # lines inside an adopted shared head are already resident here:
        # a catch-up sync never re-moves them (ISSUE: MirrorSync skips
        # blocks the mirror holds)
        from_line = max(from_line, self.store.shared_head_lines(rid))
        to_line = max(to_line, from_line)
        moved = self.store.copy_lines(src.store, src_slot, dst_slot,
                                      from_line, to_line)
        self.lengths[dst_slot] = src.lengths[src_slot]
        self.last_tokens[dst_slot] = src.last_tokens[src_slot]
        self.store.set_lines(rid, to_line)
        self.store.mark_synced(rid, to_line)
        return moved
