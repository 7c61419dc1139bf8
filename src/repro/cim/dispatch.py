"""Tiling dispatcher: run any PlanePack op request on a banked array.

`execute_tiled` splits an operand pair into bank-sized tiles (ArraySpec /
TilePlan from repro.cim.array), vmaps the fused backend over the tile axis,
and stitches the outputs back together — bit-exact with the untiled engine,
because elementwise CiM ops touch each word independently and tiles cut the
packed lane axis on uint32 boundaries.

Two substrate services live here as well:

  * a compiled-schedule cache: a bounded LRU of jitted programs keyed by
    schedule structure. It holds both the per-step tiled programs built
    here (key: ops, n_bits, tile shape, backend, placement) and the
    WHOLE-schedule step programs built by repro.cim.macro — one jitted XLA
    dispatch covering every access of a macro or fused region. `cache_stats()`
    exposes hit/miss/eviction counters plus `dispatches`, the number of
    jitted-program invocations (the deterministic walltime proxy the
    benchmarks gate on: a warm macro matmul is exactly ONE dispatch),
    `host_eqns`, the eqns the lowering executor bound one at a time on the
    host (repro.cim.lower), and `reduce_fold_steps`/`reduce_shift_steps`,
    the tree-reduction accesses the step programs ran as a halving fold
    or as row-buffer shifts (repro.cim.macro).
  * a `jax.shard_map` path over the production/smoke meshes of
    repro.launch.mesh: pass `mesh=` and tiles are block-distributed over the
    mesh's "data" axis, each device executing (and its ledger slice being
    charged for) only its own bank activations — multi-device execution with
    no other caller changes.

The ledger is charged per (device, bank) activation (see
repro.cim.accounting), which is what makes the contention-adjusted EDP
projection and the per-device ledger sum-check possible.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import engine, opset
from . import array as array_mod
from .accounting import LEDGER
from .array import DEFAULT_SPEC, ArraySpec, TilePlan
from .backends import Backend, get_backend
from .planepack import PlanePack


# ---------------------------------------------------------------------------
# compiled-schedule cache (bounded LRU)
# ---------------------------------------------------------------------------

import os as _os
from collections import OrderedDict

#: default capacity; override per process with set_schedule_cache_capacity()
#: or the REPRO_CIM_CACHE_CAPACITY env var. Serving workloads with varied
#: tile shapes would otherwise grow the program table without bound.
_DEFAULT_CAPACITY = 256


class BoundedLRU:
    """Move-to-front bounded mapping with hit/miss/eviction counters — the
    schedule-program table's caching policy, factored out so other
    structural-key caches (the autotuner's winners table) share one
    implementation. An insert past capacity evicts the coldest entry;
    correctness must never depend on residency."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        if capacity < 1:
            raise opset.CimOpError(
                f"cache capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._data: "OrderedDict[object, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key, default=None):
        """Look up, counting a hit (and refreshing recency) or a miss.
        Callers that miss MUST build and `put` under the same key."""
        if key in self._data:
            self.hits += 1
            self._data.move_to_end(key)
            return self._data[key]
        self.misses += 1
        return default

    def put(self, key, value) -> None:
        self._data[key] = value
        self._data.move_to_end(key)
        while len(self._data) > self.capacity:
            self._data.popitem(last=False)
            self.evictions += 1

    def set_capacity(self, capacity: int) -> None:
        if capacity < 1:
            raise opset.CimOpError(
                f"cache capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        while len(self._data) > self.capacity:
            self._data.popitem(last=False)
            self.evictions += 1

    def clear(self) -> None:
        self._data.clear()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def items(self):
        return self._data.items()

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._data), "evictions": self.evictions,
                "capacity": self.capacity}

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        return key in self._data


_PROGRAMS: "OrderedDict[tuple, object]" = OrderedDict()


def _env_capacity() -> int:
    """REPRO_CIM_CACHE_CAPACITY, validated like set_schedule_cache_capacity
    (malformed or < 1 values fall back to the default instead of silently
    disabling the cache or crashing the import)."""
    raw = _os.environ.get("REPRO_CIM_CACHE_CAPACITY")
    if raw is None:
        return _DEFAULT_CAPACITY
    try:
        cap = int(raw)
    except ValueError:
        return _DEFAULT_CAPACITY
    return cap if cap >= 1 else _DEFAULT_CAPACITY


_CAPACITY = _env_capacity()
_HITS = 0
_MISSES = 0
_EVICTIONS = 0
_DISPATCHES = 0
_HOST_EQNS = 0
_REDUCE_FOLD_STEPS = 0
_REDUCE_SHIFT_STEPS = 0


def cache_stats() -> Dict[str, int]:
    """Counters of the compiled-schedule cache: hits/misses/evictions of
    the program table plus `dispatches`, the total number of jitted-program
    invocations (whole-schedule step programs and per-step tiled programs
    alike). A warm macro or fused region costs exactly one dispatch.
    `host_eqns` counts the eqns lowered functions executed on the host,
    each its own eager dispatch (eqns a warm resident call skips are not
    counted). `reduce_fold_steps` and `reduce_shift_steps` count the
    tree-reduction accesses step programs executed as a halving fold and
    as row-buffer shifts, per invocation (eager cursors are not counted).
    Resident-region counters (resident_pins/hits/misses/evictions/
    invalidations, aggregated across every ResidentSet) ride along so one
    call answers both "did the program cache stay warm" and "did the
    operands stay pinned"."""
    stats = {"hits": _HITS, "misses": _MISSES, "entries": len(_PROGRAMS),
             "evictions": _EVICTIONS, "capacity": _CAPACITY,
             "dispatches": _DISPATCHES, "host_eqns": _HOST_EQNS,
             "reduce_fold_steps": _REDUCE_FOLD_STEPS,
             "reduce_shift_steps": _REDUCE_SHIFT_STEPS}
    stats.update(array_mod.resident_stats())
    from . import faults as faults_mod

    stats.update(faults_mod.fault_stats())
    return stats


def clear_schedule_cache() -> None:
    global _HITS, _MISSES, _EVICTIONS, _DISPATCHES, _HOST_EQNS, \
        _REDUCE_FOLD_STEPS, _REDUCE_SHIFT_STEPS
    _PROGRAMS.clear()
    _HITS = 0
    _MISSES = 0
    _EVICTIONS = 0
    _DISPATCHES = 0
    _HOST_EQNS = 0
    _REDUCE_FOLD_STEPS = 0
    _REDUCE_SHIFT_STEPS = 0


def count_dispatch(n: int = 1) -> None:
    """Record `n` jitted-program invocations (see cache_stats)."""
    global _DISPATCHES
    _DISPATCHES += n


def count_host_eqns(n: int) -> None:
    """Record `n` eqns executed on the host by a lowered function."""
    global _HOST_EQNS
    _HOST_EQNS += n


def count_reduce_steps(fold: int, shift: int) -> None:
    """Record one program invocation's tree-reduction accesses: `fold` run
    as a halving fold, `shift` as row-buffer shifts."""
    global _REDUCE_FOLD_STEPS, _REDUCE_SHIFT_STEPS
    _REDUCE_FOLD_STEPS += fold
    _REDUCE_SHIFT_STEPS += shift


def program_cache_get(key):
    """Look up a compiled program, counting a hit (and refreshing LRU
    recency) or a miss. Callers that miss MUST build and `program_cache_put`
    under the same key."""
    global _HITS, _MISSES
    prog = _PROGRAMS.get(key)
    if prog is not None:
        _HITS += 1
        _PROGRAMS.move_to_end(key)
        return prog
    _MISSES += 1
    return None


def program_cache_put(key, prog) -> None:
    _PROGRAMS[key] = prog
    _evict_to_capacity()


def set_schedule_cache_capacity(capacity: int) -> None:
    """Bound the compiled-schedule cache to `capacity` entries (>= 1);
    least-recently-used programs are evicted once the bound is exceeded."""
    global _CAPACITY
    if capacity < 1:
        raise opset.CimOpError(f"cache capacity must be >= 1, got {capacity}")
    _CAPACITY = int(capacity)
    _evict_to_capacity()


def _evict_to_capacity() -> None:
    global _EVICTIONS
    while len(_PROGRAMS) > _CAPACITY:
        _PROGRAMS.popitem(last=False)
        _EVICTIONS += 1


def _cached_program(ops: Tuple[str, ...], n_bits: int, tile_shape: tuple,
                    bk: Backend, mesh, axis: Optional[str]):
    """The jitted tiled program for one schedule key.

    Without the cache every call would close over a fresh lambda and retrace
    under jit; with it, a repeated (ops, n_bits, tile_shape, backend[,mesh])
    schedule reuses the compiled executable. The table is a bounded LRU:
    a hit refreshes recency, an insert past capacity evicts the coldest
    program (it recompiles on next use — correctness never depends on
    residency)."""
    # the mesh object itself (hashable) is the key component: two meshes of
    # identical shape over DIFFERENT devices must not share a program
    key = (ops, n_bits, tile_shape, bk.name,
           None if mesh is None else (mesh, axis))
    prog = program_cache_get(key)
    if prog is not None:
        return prog

    prog = jax.jit(_tiled_body(ops, bk, mesh, axis))
    program_cache_put(key, prog)
    return prog


def _tiled_body(ops: Tuple[str, ...], bk: Backend, mesh, axis):
    """The (unjitted) tiled computation: vmap the fused backend over the
    tile axis, shard_mapped over `axis` when a mesh is given. Shared by the
    eager per-step program above and the traced whole-schedule path below
    (where the enclosing step program provides the jit)."""

    def tiled(ta, tb):
        return jax.vmap(lambda ap, bp: bk.fn(ap, bp, ops))(ta, tb)

    if mesh is None:
        return tiled
    from jax.sharding import PartitionSpec as P

    spec3 = P(axis, None, None)
    return jax.shard_map(tiled, mesh=mesh, in_specs=(spec3, spec3),
                         out_specs=tuple(spec3 for _ in ops), check_vma=False)


# ---------------------------------------------------------------------------
# tile / untile (packed lane axis, uint32 boundaries)
# ---------------------------------------------------------------------------


def _tile(planes: jax.Array, plan: TilePlan, n_tiles: int) -> jax.Array:
    """uint32[n_bits, W] -> uint32[n_tiles, n_bits, lanes_per_tile]."""
    n_bits, w = planes.shape
    pad = n_tiles * plan.lanes_per_tile - w
    if pad:
        planes = jnp.pad(planes, ((0, 0), (0, pad)))
    return planes.reshape(n_bits, n_tiles, plan.lanes_per_tile) \
                 .transpose(1, 0, 2)


def _untile(raw: jax.Array, w: int) -> jax.Array:
    """uint32[n_tiles, rows, lanes] -> uint32[rows, W] (pad lanes dropped)."""
    n_tiles, rows, lanes = raw.shape
    return raw.transpose(1, 0, 2).reshape(rows, n_tiles * lanes)[:, :w]


# ---------------------------------------------------------------------------
# the dispatcher
# ---------------------------------------------------------------------------


def _prepare_tiles(a: PlanePack, b: PlanePack, ops: Sequence[str],
                   spec: Optional[ArraySpec], mesh, axis: str):
    """Shared front half of the tiled paths: operand alignment, geometry
    checks, tile placement and the padded tile stacks."""
    a, b, ops = engine.prepare_operands(a, b, ops)
    spec = spec or DEFAULT_SPEC
    # combined budget: access planes must fit alongside whatever the
    # process-wide resident region for this geometry has pinned in rows
    spec.check_fits(a.n_bits, ops,
                    resident_rows=array_mod.resident_rows_for(spec))
    plan = spec.plan(a.n_words)

    n_devices = 1
    exec_tiles = plan.n_tiles
    if mesh is not None:
        if axis not in mesh.axis_names:
            raise opset.CimOpError(
                f"mesh has axes {mesh.axis_names}, no {axis!r}")
        n_devices = int(mesh.shape[axis])
        # block placement: pad the tile axis so every device owns the same
        # number of tiles; pad tiles hold no operands and are not charged
        exec_tiles = -(-plan.n_tiles // n_devices) * n_devices

    ta = _tile(a.planes, plan, exec_tiles)
    tb = _tile(b.planes, plan, exec_tiles)
    return a, b, ops, plan, n_devices, ta, tb


def _fault_overlay(a: PlanePack, b: PlanePack, plan: TilePlan,
                   ta, tb, exec_tiles: int):
    """Transient-fault injection on the STREAMED operands of one eager
    tiled access (BER flips + stuck-at rows of the active FaultModel).
    Faults are injected only on concrete values — inside a trace the
    operands pass through untouched (a flip baked into a compiled program
    would replay forever, which is not a fault model)."""
    from . import faults as faults_mod

    fm = faults_mod.active()
    if fm is None or (fm.config.ber <= 0.0 and not fm.config.stuck):
        return a, b, ta, tb
    if isinstance(a.planes, jax.core.Tracer) \
            or isinstance(b.planes, jax.core.Tracer):
        return a, b, ta, tb
    import dataclasses as _dc

    import numpy as np

    pa, na = fm.corrupt_streamed(np.asarray(a.planes), plan)
    pb, nb = fm.corrupt_streamed(np.asarray(b.planes), plan)
    if na:
        a = _dc.replace(a, planes=jnp.asarray(pa))
        ta = _tile(a.planes, plan, exec_tiles)
    if nb:
        b = _dc.replace(b, planes=jnp.asarray(pb))
        tb = _tile(b.planes, plan, exec_tiles)
    return a, b, ta, tb


def _wrap_tiled(a: PlanePack, ops: Tuple[str, ...],
                raws) -> engine.Outputs:
    w = a.planes.shape[1]
    return {op: engine._wrap(op, _untile(raw, w), a.n_bits, a.shape)
            for op, raw in zip(ops, raws)}


def execute_tiled(a: PlanePack, b: PlanePack, ops: Sequence[str],
                  spec: Optional[ArraySpec] = None,
                  backend: Optional[str] = None,
                  mesh=None, axis: str = "data") -> engine.Outputs:
    """One logical ADRA access on a banked array: bank-sized tiles, vmapped
    (and, with `mesh`, shard_mapped over its `axis`) over the fused backend.

    Bit-exact with engine.execute; the difference is physical: the ledger is
    charged one activation per tile, attributed to (device, bank), and the
    last tile's idle columns are charged as activated-but-idle words.
    """
    a, b, ops, plan, n_devices, ta, tb = _prepare_tiles(
        a, b, ops, spec, mesh, axis)
    a, b, ta, tb = _fault_overlay(a, b, plan, ta, tb,
                                  exec_tiles=ta.shape[0])
    bk = get_backend(backend)
    prog = _cached_program(ops, a.n_bits, tuple(ta.shape[1:]), bk,
                           mesh, axis if mesh is not None else None)
    raws = prog(ta, tb)
    count_dispatch()      # invoke first, account after (as CompiledSchedule)

    LEDGER.charge_banked(ops, a.n_bits, a.n_words, plan,
                         n_devices=n_devices)
    return _wrap_tiled(a, ops, raws)


def execute_tiled_traced(a: PlanePack, b: PlanePack, ops: Sequence[str],
                         spec: Optional[ArraySpec] = None,
                         backend: Optional[str] = None,
                         mesh=None, axis: str = "data",
                         charges: Optional[list] = None) -> engine.Outputs:
    """The side-effect-free inner form of `execute_tiled`: the same tiled
    (and shard_mapped) computation applied INLINE — no inner jit, no ledger
    mutation — so a whole-schedule step program can trace banked accesses
    into one XLA dispatch. With `charges`, appends the charge-from-plan
    record `execute_tiled` would have applied."""
    a, b, ops, plan, n_devices, ta, tb = _prepare_tiles(
        a, b, ops, spec, mesh, axis)
    bk = get_backend(backend)
    raws = _tiled_body(ops, bk, mesh, axis if mesh is not None else None)(
        ta, tb)
    if charges is not None:
        charges.append(("banked", ops, a.n_bits, a.n_words, plan, n_devices))
    return _wrap_tiled(a, ops, raws)


def execute_sharded(a: PlanePack, b: PlanePack, ops: Sequence[str], mesh,
                    spec: Optional[ArraySpec] = None,
                    backend: Optional[str] = None,
                    axis: str = "data") -> engine.Outputs:
    """`execute_tiled` with a mandatory mesh (the multi-device entry point —
    make_smoke_mesh / make_production_mesh from repro.launch.mesh)."""
    return execute_tiled(a, b, ops, spec=spec, backend=backend,
                         mesh=mesh, axis=axis)
