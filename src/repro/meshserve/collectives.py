"""Device-to-device redundancy transfers between mesh slices.

``MirrorSync`` / ``StreamState`` / the Splitwise handoff move KV state
between *instances*.  When the instances live on disjoint mesh slices,
the bytes must ride the device interconnect — never a host round-trip on
the serving fast path.  The primitives here are the one place that
movement happens:

* gather the rows on the source slice (a jitted slice-local read),
* :func:`device_transfer` them onto the destination slice under a
  ``transfer_guard_device_to_host("disallow")`` — an accidental host
  bounce raises instead of silently serializing the pool,
* scatter them into the destination pool (jitted, destination donated).

Every copy is counted in the module-level :data:`STATS`
(:class:`TransferStats`) so tests can assert the fast path stayed on
device (``host_copies == 0``) and benchmarks can report moved bytes.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.state import line_index


@dataclass
class TransferStats:
    """Counters over every cross-slice transfer since the last reset."""

    d2d_copies: int = 0      #: transfers that stayed device-to-device
    d2d_bytes: int = 0       #: payload bytes of those transfers
    host_copies: int = 0     #: transfers that fell back through the host

    def reset(self) -> None:
        self.d2d_copies = 0
        self.d2d_bytes = 0
        self.host_copies = 0


#: the transfer-guard counter: one per process, like jax's own guards
STATS = TransferStats()


def _replicated_like(sharding):
    """A replicated placement over the same device set as ``sharding``.
    Compiled outputs may carry GSPMD (rank-specific) shardings, so the
    fallback rebuilds a rank-agnostic placement from the device list."""
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, P())
    devs = getattr(sharding, "_device_assignment", None)
    if devs:
        return NamedSharding(jax.sharding.Mesh(np.asarray(devs), ("slice",)),
                             P())
    return sharding      # single-device placements are already concrete


def device_transfer(x, like):
    """Move ``x`` onto the device set backing array ``like`` (replicated
    there; a following slice-local op reshards as needed).  The transfer
    guard turns a host round-trip into an error — the fallback path is
    counted, not hidden, so the fast-path contract stays testable."""
    dst = getattr(like, "sharding", None)
    if dst is None:
        return x
    src = getattr(x, "sharding", None)
    if src is not None and src.device_set == dst.device_set:
        return x
    target = _replicated_like(dst)
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            out = jax.device_put(x, target)
            out.block_until_ready()
    except jax.errors.JaxRuntimeError as e:
        # only the guard's own refusal falls back; any other failure is
        # a real fault and propagates
        if "Disallowed device-to-host transfer" not in str(e):
            raise
        STATS.host_copies += 1
        out = jax.device_put(np.asarray(x), target)
    else:
        STATS.d2d_copies += 1
        STATS.d2d_bytes += int(x.size) * x.dtype.itemsize
    return out


# slice-local jitted halves of the cross-slice copies.  The gather runs
# on the source slice, the scatter on the destination (its pool leaf is
# donated so the update is in place); the device_transfer between them
# is the only inter-slice hop.


@functools.partial(jax.jit, static_argnames=("axis",))
def _gather_rows(src, slot, pos, axis):
    return src[line_index(slot, pos, axis)]


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("axis",))
def _scatter_rows(dst, rows, slot, pos, axis):
    return dst.at[line_index(slot, pos, axis)].set(rows)


@jax.jit
def _gather_entry(src, slot):
    return src[:, slot]


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_entry(dst, rows, slot):
    return dst.at[:, slot].set(rows)


def pull_rows(dst, src, dst_slot: int, src_slot: int, pos, axis: int):
    """Cross-slice form of the mirror's row copy: ``src``'s KV rows
    ``pos`` (on line ``axis``) of ``src_slot`` land in ``dst``'s
    ``dst_slot``."""
    rows = _gather_rows(src, jnp.int32(src_slot), pos, axis=axis)
    rows = device_transfer(rows, dst)
    return _scatter_rows(dst, rows, jnp.int32(dst_slot), pos, axis=axis)


def pull_entry(dst, src, dst_slot: int, src_slot: int):
    """Cross-slice form of the constant-size state copy (recurrent
    leaves)."""
    rows = _gather_entry(src, jnp.int32(src_slot))
    rows = device_transfer(rows, dst)
    return _scatter_entry(dst, rows, jnp.int32(dst_slot))


def same_devices(a, b) -> bool:
    """Whether two arrays are backed by the same device set (the gate
    between the slice-local copy jits and the cross-slice pulls)."""
    sa = getattr(a, "sharding", None)
    sb = getattr(b, "sharding", None)
    if sa is None or sb is None:
        return True
    return sa.device_set == sb.device_set
