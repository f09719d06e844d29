"""PagedStore: the live engine's block-table KV/state store.

Owns the serving-state arrays for one instance (the pytree
``repro.models.init_state`` builds) *and* the block ledger over them.
Physical layout is **slot-affine**: each request slot owns a contiguous
region of the pool — one fixed block for its recurrent/static state
(when the architecture has any) followed by ``kv_capacity /
block_lines`` line blocks backing rows of the dense cache window — so
the model's layer-scan state layout is untouched while allocation,
headroom and eviction are block-granular.  The block tables this yields
are real: :meth:`line_block_table` feeds the paged decode-attention
kernel (``repro.kernels.decode_attention.paged_decode_attention_pallas``)
which gathers K/V through them on the TPU path.

The store executes the two redundancy data movements in *line* units:

* :meth:`copy_lines` — the per-step mirror: only the KV rows in
  ``[from_line-1, to_line-1)`` move (accounting lines count the reserved
  next-token line, hence the -1 shift to written rows; see
  ``kvstore.base``), plus the constant-size recurrent states.  O(delta)
  per step, not O(kv_capacity).
* :meth:`stream_slot` / :meth:`import_chunk` — whole-state transfers as
  per-layer chunks, the unit the mesh overlaps with prefill compute
  (AcceLLM §4.2.4).

When the two stores live on different mesh slices (``repro.meshserve``:
each instance's pool is committed to its own device set), both movements
switch from the slice-local copy jits to the collective pulls in
``repro.meshserve.collectives`` — gather on the source slice, one
device-to-device hop, scatter on the destination — so redundancy traffic
never bounces through the host.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.kvstore.base import BlockLedger, KVStoreError, LineCosts
from repro.models import init_state
from repro.models.blocks import layer_specs, plan_segments
from repro.models.state import LINE_AXIS, line_index, pool_view


def pick_block_lines(kv_capacity: int, requested: int = 16) -> int:
    """Largest divisor of the cache window that is <= ``requested``."""
    b = max(1, min(requested, kv_capacity))
    while kv_capacity % b:
        b -= 1
    return b


# jitted copy primitives for the mirror hot path: slot indices and row
# positions are traced (one compile per (shape, n_rows), reused every
# step); the destination buffer is donated so the update is in place.


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("axis",))
def _copy_rows(dst, src, dst_slot, src_slot, pos, axis):
    return dst.at[line_index(dst_slot, pos, axis)].set(
        src[line_index(src_slot, pos, axis)])


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_entry(dst, src, dst_slot, src_slot):
    return dst.at[:, dst_slot].set(src[:, src_slot])


# intra-store gather for prefix adoption: source rows live in *other*
# slots' windows (one per shared block), so the row vector carries its
# own per-row slot index; not donated — src and dst are the same buffer
@functools.partial(jax.jit, static_argnames=("axis",))
def _gather_rows(arr, dst_slot, src_slots, src_pos, dst_pos, axis):
    return arr.at[line_index(dst_slot, dst_pos, axis)].set(
        arr[line_index(src_slots, src_pos, axis)])


def _colocated(a, b) -> bool:
    """Whether two leaves share a device set (the slice-local fast
    path); differing sets route through the meshserve collectives."""
    sa = getattr(a, "sharding", None)
    sb = getattr(b, "sharding", None)
    if sa is None or sb is None:
        return True
    return sa.device_set == sb.device_set


class PagedStore:
    def __init__(self, cfg: ModelConfig, num_slots: int, kv_capacity: int,
                 block_lines: Optional[int] = None,
                 dtype_name: Optional[str] = None):
        self.cfg = cfg
        self.num_slots = num_slots
        self.kv_capacity = kv_capacity
        if block_lines is not None and kv_capacity % block_lines:
            # an explicit geometry request must not be silently rounded
            raise KVStoreError(
                f"block_lines {block_lines} does not divide "
                f"kv_capacity {kv_capacity}")
        self.block_lines = pick_block_lines(kv_capacity, block_lines or 16)
        self.costs = LineCosts.from_config(cfg)
        self.line_blocks_per_slot = kv_capacity // self.block_lines
        self._has_fixed = self.costs.fixed_bytes > 0
        self.blocks_per_slot = self.line_blocks_per_slot + (
            1 if self._has_fixed else 0)
        self.ledger = BlockLedger(
            self.costs, num_blocks=num_slots * self.blocks_per_slot,
            block_lines=self.block_lines,
            max_blocks_per_seq=self.line_blocks_per_slot)
        self.state = init_state(cfg, num_slots, kv_capacity,
                                dtype_name=dtype_name)
        self.slot_rid: Dict[int, int] = {}
        self.rid_slot: Dict[int, int] = {}
        # leaf classification: (segment index, part key, leaf key, kind)
        # with kind "line" / "static" / "recurrent"
        self._paths: List[Tuple[int, str, str, str]] = []
        for i, seg in enumerate(plan_segments(layer_specs(cfg))):
            for j, spec in enumerate(seg.specs):
                for key in self.state["layers"][i][f"p{j}"]:
                    if spec.block == "attn":
                        kind = "line" if key in LINE_AXIS else "static"
                    else:
                        kind = "recurrent"
                    self._paths.append((i, f"p{j}", key, kind))

    # -- capacity ------------------------------------------------------------
    @property
    def capacity_bytes(self) -> float:
        """Accounting capacity: every slot filled to the cache window."""
        return self.num_slots * self.costs.bytes_at(self.kv_capacity)

    def used_bytes(self) -> float:
        return self.ledger.used_bytes()

    def used_bytes_of(self, rid: int) -> float:
        return self.ledger.used_bytes_of(rid)

    def free_bytes(self) -> float:
        return self.capacity_bytes - self.ledger.used_bytes()

    def free_blocks(self) -> int:
        return self.ledger.free_blocks()

    # -- block tables ----------------------------------------------------------
    def slot_block_ids(self, slot: int) -> List[int]:
        lo = slot * self.blocks_per_slot
        return list(range(lo, lo + self.blocks_per_slot))

    def line_block_table(self, rid: int) -> List[int]:
        """Physical *line-block* ids of ``rid`` in pool numbering (block
        ``slot * kv_capacity/block_lines + j`` is line block ``j`` of the
        slot's window, see :meth:`pool_view`), the table the paged decode
        kernel gathers through."""
        off = 1 if self._has_fixed else 0
        out = []
        for b in self.ledger.tables[rid]:
            slot, k = divmod(b, self.blocks_per_slot)
            out.append(slot * self.line_blocks_per_slot + (k - off))
        return out

    def decode_block_tables(self, rids: List[int], blocks: int):
        """Padded ``(len(rids), blocks)`` int32 block tables for the
        paged decode kernel.  Slot-affine placement makes each row the
        identity run over its slot's pool region — the blocks the ring
        window will hand the request as it grows — so one table covers
        a whole fused multi-step scan without re-planning mid-scan
        (``line_block_table`` returns exactly the allocated prefix of
        this run).  Entries past a request's live lines are masked by
        the kernel's ``lengths`` scalar, never read as valid KV."""
        import numpy as np
        blocks = min(blocks, self.line_blocks_per_slot)
        out = np.empty((len(rids), blocks), np.int32)
        for i, rid in enumerate(rids):
            base = self.rid_slot[rid] * self.line_blocks_per_slot
            out[i] = np.arange(base, base + blocks, dtype=np.int32)
        return out

    def pool_view(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Reshape one request-batched head-major cache leaf ``(B, KVH, W,
        hd)`` into the block pool ``(B, KVH, W/block_lines, block_lines,
        hd)`` addressed by :meth:`line_block_table` (a reshape, no
        copy)."""
        return pool_view(arr, self.block_lines)

    # -- ledger ops (slot-affine) ----------------------------------------------
    def alloc(self, rid: int, slot: int, lines: int,
              synced: Optional[int] = None,
              shared: Optional[List[int]] = None) -> None:
        """Admit ``rid`` into ``slot``.  ``shared`` adopts a resident
        prefix's blocks (anywhere in the pool) as the table head; the
        slot's *own* region then backs only the suffix — but note the
        physical contract: the dense window stays self-contained, so the
        caller must also :meth:`copy_prefix` the shared rows into the
        slot's window.  The slot's own blocks shadowed by the shared
        head (logical positions ``[0, len(shared))``) hold those copied
        rows and stay OFF the ledger — the ledger is the accounting
        truth, and sharing is exactly the HBM it saves."""
        if slot in self.slot_rid:
            raise KVStoreError(f"slot {slot} already backs "
                               f"rid {self.slot_rid[slot]}")
        ids = self.slot_block_ids(slot)
        off = 1 if self._has_fixed else 0
        n_shared = len(shared) if shared else 0
        if n_shared:
            if n_shared * self.block_lines > min(lines, self.kv_capacity):
                raise KVStoreError(
                    f"rid {rid}: shared head {n_shared} blocks exceeds "
                    f"{lines} lines (hits must be block-aligned)")
            hint = ids[:off] + ids[off + n_shared:]
        else:
            hint = ids
        self.ledger.alloc(rid, lines, block_ids=hint, synced=synced,
                          shared=shared)
        self.slot_rid[slot] = rid
        self.rid_slot[rid] = slot

    def _grow_hint(self, rid: int) -> List[int]:
        """Free own-region blocks for the *next* logical positions of
        ``rid`` — skipping the positions shadowed by a shared head, whose
        own blocks hold the copied prefix rows and must never be handed
        out as growth."""
        slot = self.rid_slot[rid]
        ids = self.slot_block_ids(slot)
        off = 1 if self._has_fixed else 0
        return ids[off + len(self.ledger.tables[rid]):]

    def append_line(self, rid: int, n: int = 1) -> int:
        out = self.ledger.append_line(rid, n,
                                      block_ids=self._grow_hint(rid))
        if self.ledger.last_cow is not None:
            raise KVStoreError(
                f"rid {rid}: copy-on-write inside the slot-affine store "
                f"(shared heads must be block-aligned)")
        return out

    def set_lines(self, rid: int, lines: int) -> int:
        cur = self.ledger.lines(rid)
        if lines > cur:
            return self.append_line(rid, lines - cur)
        return self.ledger.set_lines(rid, lines)

    def free_slot(self, slot: int) -> int:
        """Release the slot's request; returns blocks *actually* freed
        (shared blocks survive under their other referents)."""
        rid = self.slot_rid.pop(slot, None)
        if rid is None:
            return 0
        self.rid_slot.pop(rid)
        return self.ledger.free(rid)

    def slot_used_blocks(self, slot: int) -> List[int]:
        """Own-region blocks still referenced (by a table or the prefix
        cache) — a slot is reusable for fresh prefill only once this is
        empty."""
        return [b for b in self.slot_block_ids(slot)
                if self.ledger.refcount(b) > 0]

    def shared_head_lines(self, rid: int) -> int:
        return self.ledger.shared_head_lines(rid)

    def shared_saved_bytes(self) -> float:
        """HBM the refcounted prefix sharing avoids allocating:
        Σ (refs − 1) blocks at block granularity."""
        return self.ledger.shared_saved_blocks() * self.ledger.block_bytes

    def lines(self, rid: int) -> int:
        return self.ledger.lines(rid)

    def synced_line(self, rid: int) -> int:
        return self.ledger.synced_line(rid)

    def delta_since(self, rid: int, line: int) -> Tuple[int, int]:
        return self.ledger.delta_since(rid, line)

    def mark_synced(self, rid: int, line: Optional[int] = None):
        self.ledger.mark_synced(rid, line)

    # -- whole-slot state movement ---------------------------------------------
    def extract_slot(self, slot: int):
        """Per-request state (batch dim kept, size 1)."""

        def ex(a):
            return a[:, slot: slot + 1]

        out = {"layers": jax.tree_util.tree_map(ex, self.state["layers"])}
        if "enc_out" in self.state:
            out["enc_out"] = self.state["enc_out"][slot: slot + 1]
        return out

    def merge_slot(self, slot: int, sub_state, src_slot: int = 0):
        """Install ``sub_state`` (batch dim 1 at ``src_slot``) into
        ``slot``, whole-window (row bounds clamp to the smaller of the
        two cache windows).  Batch is dim 1 for layer states (dim 0 is
        the segment repeat dim) and dim 0 for ``enc_out``."""
        self.merge_slot_rows(slot, sub_state, 0, self.kv_capacity,
                             src_slot=src_slot)

    def merge_slot_rows(self, slot: int, sub_state, lo: int, hi: int,
                        src_slot: int = 0):
        """Install ``sub_state``'s batch row ``src_slot`` into ``slot``,
        copying only KV rows ``[lo, hi)`` of the line-indexed leaves —
        the merge for bucket-sized prefill scratch (whose cache window
        may be smaller than the store's) and for resumed chunk writes.
        Recurrent and static leaves copy whole; row bounds clamp to
        whichever window is smaller."""
        with obs.span("kv.merge"):
            for i, pj, key, kind in self._paths:
                dst = self.state["layers"][i][pj][key]
                src = sub_state["layers"][i][pj][key]
                if not _colocated(dst, src):
                    from repro.meshserve import collectives
                    src = collectives.device_transfer(src, dst)
                if kind == "line":
                    ax = LINE_AXIS[key]
                    h = min(hi, src.shape[ax], dst.shape[ax])
                    l = min(lo, h)
                    if h <= l:
                        continue
                    self.state["layers"][i][pj][key] = dst.at[
                        line_index(slot, slice(l, h), ax)].set(
                            src[line_index(src_slot, slice(l, h), ax)])
                else:
                    self.state["layers"][i][pj][key] = dst.at[:, slot].set(
                        src[:, src_slot])
            if "enc_out" in self.state:
                enc = sub_state["enc_out"]
                if not _colocated(self.state["enc_out"], enc):
                    from repro.meshserve import collectives
                    enc = collectives.device_transfer(
                        enc, self.state["enc_out"])
                self.state["enc_out"] = self.state["enc_out"].at[slot].set(
                    enc[src_slot])

    # -- per-layer streamed transfer (§4.2.4) ----------------------------------
    def stream_slot(self, slot: int) -> Iterator[Tuple[tuple, jnp.ndarray]]:
        """Yield ``slot``'s state one layer-part leaf at a time — the
        chunk granularity a real mesh overlaps with prefill compute."""
        for i, pj, key, _ in self._paths:
            yield ((i, pj, key), self.state["layers"][i][pj][key]
                   [:, slot: slot + 1])
        if "enc_out" in self.state:
            yield (("enc_out",), self.state["enc_out"][slot: slot + 1])

    def import_chunk(self, slot: int, path: tuple, chunk: jnp.ndarray):
        if path[0] == "enc_out":
            target = self.state["enc_out"]
            if not _colocated(target, chunk):
                from repro.meshserve import collectives
                chunk = collectives.device_transfer(chunk, target)
            self.state["enc_out"] = target.at[slot].set(chunk[0])
            return
        i, pj, key = path
        arr = self.state["layers"][i][pj][key]
        if not _colocated(arr, chunk):
            # per-layer chunk arriving from another mesh slice: one
            # device-to-device hop, then the write is slice-local
            from repro.meshserve import collectives
            chunk = collectives.device_transfer(chunk, arr)
        self.state["layers"][i][pj][key] = arr.at[:, slot].set(chunk[:, 0])

    # -- delta line copy (the §4.1.2 mirror) -----------------------------------
    def copy_lines(self, src: "PagedStore", src_slot: int, dst_slot: int,
                   from_line: int, to_line: int) -> float:
        """Copy only the KV rows of accounting lines ``[from_line,
        to_line)`` from ``src``'s slot into ours, plus the constant-size
        recurrent states; returns the bytes moved.  Accounting line ``L``
        reserves physical row ``L-1`` (the newest sampled token's KV is
        written by the *next* decode step), so rows ``[from_line-1,
        to_line-1)`` move, modulo the ring-buffer window."""
        lo, hi = max(0, from_line - 1), max(0, to_line - 1)
        n_rows = hi - lo
        d_slot = jnp.int32(dst_slot)
        s_slot = jnp.int32(src_slot)
        for i, pj, key, kind in self._paths:
            if kind == "static":
                continue
            dst_arr = self.state["layers"][i][pj][key]
            src_arr = src.state["layers"][i][pj][key]
            local = _colocated(dst_arr, src_arr)
            if not local:
                from repro.meshserve import collectives
            if kind == "recurrent":
                self.state["layers"][i][pj][key] = (
                    _copy_entry(dst_arr, src_arr, d_slot, s_slot) if local
                    else collectives.pull_entry(dst_arr, src_arr,
                                                dst_slot, src_slot))
                continue
            if n_rows <= 0:
                continue
            ax = LINE_AXIS[key]
            cap = dst_arr.shape[ax]
            pos = jnp.asarray([p % cap for p in range(lo, hi)], jnp.int32)
            self.state["layers"][i][pj][key] = (
                _copy_rows(dst_arr, src_arr, d_slot, s_slot, pos, axis=ax)
                if local
                else collectives.pull_rows(dst_arr, src_arr, dst_slot,
                                           src_slot, pos, axis=ax))
        return self.costs.mirror_bytes(max(0, to_line - from_line))

    # -- prefix adoption (one-time window fill) --------------------------------
    def copy_prefix(self, blocks: List[int], dst_slot: int,
                    n_lines: int) -> float:
        """Materialise a shared prefix run into ``dst_slot``'s dense
        window rows ``[0, n_lines)``.

        The slot-affine layout keeps each window self-contained (the
        layer scan reads its slot's rows directly), so adopting blocks
        from other slots' regions is a one-time intra-HBM row gather —
        data movement instead of prefill *compute*.  The ledger, not the
        window, is the accounting truth: the adopted blocks stay shared
        there and the shadowed own rows stay off-ledger.  Returns the
        bytes gathered (reported separately from mirror/stream traffic;
        never charged as prefill)."""
        import numpy as np
        n_lines = min(n_lines, self.kv_capacity,
                      len(blocks) * self.block_lines)
        if n_lines <= 0:
            return 0.0
        off = 1 if self._has_fixed else 0
        src_slots = np.empty((n_lines,), np.int32)
        src_pos = np.empty((n_lines,), np.int32)
        for i in range(n_lines):
            b = blocks[i // self.block_lines]
            slot, k = divmod(b, self.blocks_per_slot)
            src_slots[i] = slot
            src_pos[i] = (k - off) * self.block_lines \
                + i % self.block_lines
        dst_pos = np.arange(n_lines, dtype=np.int32)
        d_slot = jnp.int32(dst_slot)
        for i, pj, key, kind in self._paths:
            if kind != "line":
                continue
            arr = self.state["layers"][i][pj][key]
            self.state["layers"][i][pj][key] = _gather_rows(
                arr, d_slot, jnp.asarray(src_slots),
                jnp.asarray(src_pos), jnp.asarray(dst_pos),
                axis=LINE_AXIS[key])
        return self.costs.line_bytes * n_lines
