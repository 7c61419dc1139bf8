"""The jaxpr -> CiM lowering compiler: offload ESTIMATES become EXECUTION.

`lower(fn)` turns an unmodified JAX function into a hybrid callable:

  1. `repro.cim.trace` stages the function and classifies every eqn
     (single-access / multi-access / free peripheral / host).
  2. Maximal runs of eligible eqns become fused REGIONS. Each region's
     per-eqn schedules are concatenated (planner.concat_schedules) into ONE
     region Schedule, compiled by macro.run_schedule_program into ONE
     jitted XLA program: every access of every fused eqn, all the
     packed-domain peripherals between them, the entry packs and the exit
     unpacks execute as a single dispatch. Chained eligible ops share the
     program's cursor (a ChainExecutor over it) and their intermediates
     stay in the PlanePack packed domain with ZERO pack/unpack between
     them. Region programs live in the dispatch layer's bounded-LRU cache
     under a STRUCTURAL key (canonicalized dataflow + operand signatures),
     so repeated regions hit end-to-end with zero retrace; ledger charges
     replay from the trace-time PlannedCharges record. Region inputs that
     are dead after the region (intermediates, never the caller's arrays)
     are donated to the program on accelerator platforms, letting XLA reuse
     their buffers for the region's outputs of the same shape and dtype.
  3. Everything else executes on the host, eqn by eqn, exactly as
     evaluating the jaxpr would.

Each call is visible on the profiler's host plane: a `cim.call` span
(carrying the lowered function's name), a `cim.host` span around each run
of consecutive host eqns (an island, carrying the eqns it binds) and a
`cim.region` span around each region's dispatch (carrying its index). The
region programs are named `cim_<name>_r<index>` on the device plane.
`dispatch.cache_stats()["host_eqns"]` totals the islands' `eqns`, the
host eqns bound (the serve CLI prints it).

The hybrid callable is bit-exact with the original function: every CiM op
result is truncated/extended to its eqn's output dtype in the packed domain
(free peripheral wiring), so int8 wrap-around, unsigned arithmetic and bool
predicates all match jnp semantics — asserted across the full eligible op
surface by tests/test_cim_lower.py.

Cost model contract: the region schedules ARE the cost. An unbanked run
charges the ledger exactly `sum(region.schedule.accesses)` accesses — the
same number `repro.core.offload.analyze(fn, *args)` (source="jaxpr")
reports, because both read the same trace. With an ArraySpec, every access
tiles over banks through repro.cim.dispatch and the ledger charges per
(device, bank) activations instead.

The one declared exception to zero-repack: a `dot_general` consumes
MATERIALIZED integer operands (the broadcast [M, K_pad, N] layout has to be
built, exactly as in repro.cim.macro.matmul), so a packed in-region operand
feeding a contraction is unpacked first. Elementwise chains never repack.
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter, OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cost as cost_mod
from . import dispatch, macro, planner
from . import array as array_mod
from . import trace as trace_mod
from .array import ArraySpec
from .opset import CimOpError
from .planepack import PlanePack
from .trace import (CMP_PRIMS, ConstVal, DropVar, Literal, TracedOp, Var,
                    aval_of, dtype_bits, dtype_signed)

_FULL32 = np.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# packed-domain helpers (all zero-access peripheral wiring)
# ---------------------------------------------------------------------------

_PAD_MASKS: Dict[Tuple[int, int], np.ndarray] = {}


def _pad_mask(n_words: int, lanes: int) -> np.ndarray:
    m = _PAD_MASKS.get((n_words, lanes))
    if m is None:
        m = np.zeros(lanes, np.uint32)
        full, rem = divmod(n_words, 32)
        m[:full] = _FULL32
        if rem:
            m[full] = (np.uint32(1) << np.uint32(rem)) - np.uint32(1)
        _PAD_MASKS[(n_words, lanes)] = m
    return m


def _mask_pad(pack: PlanePack) -> PlanePack:
    """Zero the bit positions past the last logical word. Every region
    result is masked so packs feeding shifts/reductions keep the zero-pad
    invariant (an `eq` bitmap, say, reads 1 on pad words)."""
    lanes = pack.planes.shape[1]
    if pack.n_words >= lanes * 32:
        return pack
    mask = jnp.asarray(_pad_mask(pack.n_words, lanes))
    return dataclasses.replace(pack, planes=pack.planes & mask[None, :])


def _to_width(pack: PlanePack, bits: int, signed: bool) -> PlanePack:
    if pack.n_bits > bits:
        pack = pack.truncate_to(bits)
    elif pack.n_bits < bits:
        pack = pack.extend_to(bits)      # fill follows the pack's signedness
    return pack.as_signed(signed)


def _finish(pack: PlanePack, aval) -> PlanePack:
    """Land an eqn result on its output aval: width/signedness per dtype
    (two's-complement wrap, exactly jnp's cast semantics), logical shape,
    pad bits cleared."""
    pack = _to_width(pack, dtype_bits(aval.dtype), dtype_signed(aval.dtype))
    pack = dataclasses.replace(pack, shape=tuple(aval.shape))
    return _mask_pad(pack)


def _complement(pack: PlanePack) -> PlanePack:
    """Bitwise NOT of every plane — the SA output complement, free wiring."""
    return dataclasses.replace(pack,
                               planes=pack.planes ^ jnp.uint32(0xFFFFFFFF))


def _broadcast_pack(pack: PlanePack, shape: Tuple[int, ...]) -> PlanePack:
    """Scalar pack -> `shape`: the row buffer fanning one word out."""
    if pack.n_words != 1:
        raise CimOpError(f"can only broadcast scalar packs, got {pack.shape}")
    n = 1
    for d in shape:
        n *= int(d)
    return pack.take_words(np.zeros(n, np.int64), tuple(shape))


# ---------------------------------------------------------------------------
# regions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ResidentAtom:
    """One region input pinnable in the resident region.

    ai      : index into the region's in_atoms (== operand leaf position).
    kind    : "matmul_rhs" — every in-region consumer is a dot_general with
              this atom as its rhs, so the pinned stack is the expanded
              [M, K_pad, N] entry pack (macro.matmul_rhs_pack) and warm
              calls skip the rhs expansion AND pack entirely;
              "batched_matmul_rhs" — the batched analogue: the consumers
              are canonical batched dots and the pinned stack is the
              [B_flat * M, K_pad, N] expansion
              (macro.batched_matmul_rhs_pack) — attention's K^T / V sides;
              "pack" — the atom's plain entry pack is pinned and seeded
              into the region's pack env.
    n_words : logical words of the pinned pack (fit checks + charges).
    m       : *matmul_rhs only — the per-batch lhs row count baked into
              the pack.
    """

    ai: int
    kind: str
    n_bits: int
    signed: bool
    n_words: int
    m: int = 0
    #: matmul_rhs only — region op indices of the zero-access pass-through
    #: chain (convert/reshape) between the atom and the dot's rhs: replayed
    #: on the host when pinning, SKIPPED in the resident region body
    chain_eqns: Tuple[int, ...] = ()


@dataclasses.dataclass
class Region:
    """A maximal run of eligible eqns fused into one Schedule.

    `in_atoms` are the region program's inputs (external Vars + closed-over
    ConstVals, in first-use order; scalar Literals are baked into the
    trace). `donatable` indexes the in_atoms that are dead after the region
    — safe for jit buffer donation. `key` is the structural cache key:
    dataflow with canonicalized var numbering plus operand signatures, so
    two structurally identical regions share one compiled program.

    `resident` (set by residency planning) names the in_atoms whose entry
    packs are pinned across calls; `schedule_resident` is the same step
    plan with those operand sides named resident — a DIFFERENT Schedule
    value, so resident and streamed executions of the same region occupy
    different program-cache slots by construction."""

    name: str
    ops: List[TracedOp]
    schedule: planner.Schedule
    unpack_vars: Tuple[Any, ...] = ()   # outvars a host consumer needs
    in_atoms: Tuple[Any, ...] = ()
    donatable: Tuple[int, ...] = ()
    key: Tuple = ()
    index: int = 0
    resident: Tuple[ResidentAtom, ...] = ()
    schedule_resident: Optional[planner.Schedule] = None
    donatable_resident: Tuple[int, ...] = ()

    @property
    def accesses(self) -> int:
        return self.schedule.accesses


def _region_in_atoms(region: Region) -> Tuple[Any, ...]:
    """External operands of a region, in first-use order: Vars produced
    outside it plus ConstVals (deduped; Literals stay baked in)."""
    produced = {v for op in region.ops for v in op.outvars
                if not isinstance(v, DropVar)}
    atoms: List[Any] = []
    seen: set = set()
    for op in region.ops:
        for a in op.invars:
            if isinstance(a, Var):
                if a not in produced and a not in seen:
                    seen.add(a)
                    atoms.append(a)
            elif isinstance(a, ConstVal):
                if id(a) not in seen:
                    seen.add(id(a))
                    atoms.append(a)
    return tuple(atoms)


#: shared cache-key signature discipline (ONE definition, see macro.aval_sig)
_aval_sig = macro.aval_sig


def _buffer_sig(v) -> Tuple:
    """What decides whether XLA can reuse one buffer for another."""
    aval = aval_of(v)
    return tuple(aval.shape), str(aval.dtype)


def _region_key(region: Region) -> Tuple:
    """Structural identity of a region's traced computation.

    Vars (and ConstVals — their VALUES are program inputs, not baked
    constants) are numbered by first appearance, Literal values are hashed
    by content; together with op names and operand/result signatures this
    determines the region body's trace exactly, so structurally identical
    regions may share one compiled program."""
    ids: Dict[int, int] = {}

    def ref(v) -> int:
        return ids.setdefault(id(v), len(ids))

    parts: List[Tuple] = [
        ("in",) + tuple((ref(a), _aval_sig(aval_of(a)))
                        for a in region.in_atoms)]
    for op in region.ops:
        ins = []
        for a in op.invars:
            if isinstance(a, Literal):
                ins.append(("lit", np.asarray(a.val).tobytes(),
                            _aval_sig(a.aval)))
            else:
                ins.append(("v", ref(a), _aval_sig(aval_of(a))))
        outs = tuple(("drop",) if isinstance(v, DropVar)
                     else ("v", ref(v), _aval_sig(v.aval))
                     for v in op.outvars)
        parts.append((op.name, tuple(ins), outs))
    parts.append(("out",) + tuple(ref(v) for v in region.unpack_vars))
    return tuple(parts)


#: consumers whose getp() call always uses the operand's OWN aval shape
#: (unary source-shape reads) — safe for a penv-seeded resident pack
_SRC_SHAPE_OPS = ("reduce_sum", "convert_element_type", "reshape",
                  "broadcast_in_dim")


def _classify_resident(region: Region, ai: int, atom) -> \
        Optional[ResidentAtom]:
    """How (and whether) one derived region input can be pinned.

    "matmul_rhs" when the atom — possibly through a chain of zero-access
    unary pass-throughs (convert/reshape) with no other consumers — is
    consumed only by dot_generals taking it as rhs with one consistent
    (M, n_bits, signedness): the expanded broadcast pack is then pinnable,
    the chain eqns are replayed on the host once at pin time and skipped in
    the resident body, and the warm path skips the whole rhs build.
    Otherwise "pack" when every consumer reads the atom at its own aval
    shape (or through geti's unpack) — the plain entry pack seeds the
    region's pack env. None when the consumption pattern would need a
    per-call repack anyway (e.g. non-scalar broadcast into a wider
    elementwise shape)."""
    aval = aval_of(atom)
    consumers = [op for op in region.ops
                 if any(a is atom for a in op.invars)]
    if not consumers:                      # pragma: no cover
        return None
    # forward walk: frontier is the value the dots would consume
    frontier = atom
    chain_eqns: List[int] = []
    mk = None
    rhs_only = True
    while True:
        cons = [(ei, op) for ei, op in enumerate(region.ops)
                if any(a is frontier for a in op.invars)]
        if not cons:
            rhs_only = False
            break
        if all(op.name == "dot_general" and op.invars[1] is frontier
               and op.invars[0] is not frontier for _, op in cons):
            for _, op in cons:
                lhs_aval = aval_of(op.invars[0])
                nb = len(op.params["dimension_numbers"][1][0])
                sig = (nb, tuple(int(d) for d in lhs_aval.shape[:-1]),
                       op.n_bits, dtype_signed(lhs_aval.dtype))
                if mk is None:
                    mk = sig
                elif mk != sig:
                    rhs_only = False
                    break
            break
        ei, op = cons[0]
        if len(cons) != 1 \
                or op.name not in ("convert_element_type", "reshape") \
                or op.invars[0] is not frontier \
                or isinstance(op.outvars[0], DropVar) \
                or op.outvars[0] in region.unpack_vars:
            rhs_only = False
            break
        chain_eqns.append(ei)
        frontier = op.outvars[0]
    f_aval = aval_of(frontier)
    if rhs_only and mk is not None and len(f_aval.shape) == mk[0] + 2:
        nb, lead, n_bits, signed = mk
        # `lead` is the lhs's [*B, M]; the pinned stack holds one expanded
        # [K_pad, N] block per (batch, m) row, so the flattened row count is
        # prod(lead) and the per-batch M (what the pack builder broadcasts
        # the rhs over) is its last entry
        rows = 1
        for d in lead:
            rows *= d
        m = lead[-1]
        k, n = int(f_aval.shape[-2]), int(f_aval.shape[-1])
        k_pad = 1 << planner._log2_ceil(k)
        return ResidentAtom(ai=ai,
                            kind="batched_matmul_rhs" if nb else "matmul_rhs",
                            n_bits=n_bits, signed=signed,
                            n_words=rows * k_pad * n, m=m,
                            chain_eqns=tuple(chain_eqns))
    n_words = 1
    for d in aval.shape:
        n_words *= int(d)
    for op in consumers:
        if op.name == "dot_general" or (op.name in _SRC_SHAPE_OPS
                                        and op.invars[0] is atom):
            continue
        out_shape = tuple(aval_of(op.outvars[0]).shape)
        if out_shape != tuple(aval.shape) and n_words != 1:
            return None    # would repack at the broadcast shape per call
    return ResidentAtom(ai=ai, kind="pack",
                        n_bits=dtype_bits(aval.dtype),
                        signed=dtype_signed(aval.dtype), n_words=n_words)


#: a resident atom's plain entry pack, built as one program (see
#: macro.matmul_rhs_pack)
_pack_program = jax.jit(PlanePack.pack, static_argnames=("n_bits", "signed"))


def _read_host(env: Dict[Any, Any], atom):
    if isinstance(atom, Literal):
        return jnp.asarray(atom.val, dtype=atom.aval.dtype)
    if isinstance(atom, ConstVal):
        return atom.val
    return env[atom]


class LoweredComputation:
    """One staged-and-planned lowering of a function at fixed avals.

    `execute(*args)` runs the hybrid program; `describe()` prints the
    region structure and fused schedules; `accesses` is the exact unbanked
    ledger charge of one execution. `name` labels its spans and region
    programs.
    """

    def __init__(self, tr: trace_mod.Trace,
                 backend: Optional[str] = None,
                 spec: Optional[ArraySpec] = None, mesh=None,
                 resident_leaf_idx: Tuple[int, ...] = (),
                 resident_set=None, policy: Optional[str] = None,
                 device=None, name: str = "fn"):
        self.trace = tr
        self.name = name
        self.backend = backend
        self.spec = spec
        self.mesh = mesh
        self.resident_leaf_idx = tuple(resident_leaf_idx)
        # resident_set=None -> the registry set for `spec`: resolved fresh
        # on every execute (clear_resident/set_resident_ecc/failover swap
        # the registry object; stale captures would pin unprotected), and
        # once here for the construction-time residency budget planning
        self._registry_rs = resident_set is None
        if resident_set is None and self.resident_leaf_idx:
            resident_set = array_mod.lowering_resident_set(spec)
        self.resident_set = resident_set
        # the cost model decides, per eligible eqn, whether lowering pays
        # under `policy` (repro.cim.cost); demoted eqns run on host
        self.offload_plan = cost_mod.plan_offload(
            tr, spec=spec, device=device, policy=policy)
        self.policy = self.offload_plan.policy
        self.items: List[Tuple[str, Any]] = []
        self.regions: List[Region] = []
        self._warm_skip: frozenset = frozenset()
        self._build()
        self._plan_residency()
        self._steps = self._plan_islands()

    # -- structure ----------------------------------------------------------
    def _build(self) -> None:
        items: List[Tuple[str, Any]] = []
        buf: List[TracedOp] = []

        def flush():
            if not buf:
                return
            scheds = [o.schedule for o in buf if o.schedule is not None]
            if not scheds or sum(s.accesses for s in scheds) == 0:
                # a run of purely-free eqns does no array work: host it
                items.extend(("host", o) for o in buf)
            else:
                # the schedule's macro name is deliberately NOT positional:
                # it is part of the program-cache key, and structurally
                # identical regions (e.g. repeated layers) must share one
                # compiled program — Region.name keeps the position for
                # display
                region = Region(name=f"region{len(self.regions)}",
                                ops=list(buf),
                                schedule=planner.concat_schedules(
                                    scheds, macro="region"),
                                index=len(self.regions))
                self.regions.append(region)
                items.append(("region", region))
            buf.clear()

        demoted = self.offload_plan.demoted
        for i, op in enumerate(self.trace.ops):
            if op.eligible and i not in demoted:
                buf.append(op)
            else:
                flush()
                items.append(("host", op))
        flush()
        self.items = items

        # which region outputs must materialize for host consumers / outputs
        out_roots = {v for v in self.trace.closed.jaxpr.outvars
                     if isinstance(v, Var)}
        consumed_after: List[set] = [set() for _ in items]
        acc: set = set(out_roots)
        for i in range(len(items) - 1, -1, -1):
            consumed_after[i] = set(acc)
            kind, payload = items[i]
            ops = payload.ops if kind == "region" else [payload]
            for op in ops:
                acc.update(v for v in op.invars
                           if isinstance(v, Var))
        caller_owned = set(self.trace.closed.jaxpr.invars) \
            | set(self.trace.closed.jaxpr.constvars)
        # an _alias eqn (jit-inlining passthrough) binds its outvar to the
        # SAME jax.Array as its source — caller arguments and still-live
        # vars included — so any var touching an alias is unsafe to donate
        alias_tainted: set = set()
        for op in self.trace.ops:
            if op.name == "_alias":
                alias_tainted.update(
                    v for v in op.invars if isinstance(v, Var))
                alias_tainted.update(
                    v for v in op.outvars
                    if not isinstance(v, DropVar))
        for i, (kind, payload) in enumerate(items):
            if kind == "region":
                payload.unpack_vars = tuple(
                    v for op in payload.ops for v in op.outvars
                    if v in consumed_after[i])
                payload.in_atoms = _region_in_atoms(payload)
                # inputs dead after this region (and neither the caller's
                # own buffers nor alias-shared ones) may be donated to the
                # compiled region program; XLA reuses a donated buffer only
                # for an output of its shape and dtype, so donate no more
                # than the outputs can take
                free = Counter(_buffer_sig(v) for v in payload.unpack_vars)
                donatable = []
                for j, a in enumerate(payload.in_atoms):
                    if (isinstance(a, Var) and a not in caller_owned
                            and a not in alias_tainted
                            and a not in consumed_after[i]
                            and free[_buffer_sig(a)] > 0):
                        free[_buffer_sig(a)] -= 1
                        donatable.append(j)
                payload.donatable = tuple(donatable)
                payload.key = _region_key(payload)

    # -- residency planning -------------------------------------------------
    def _plan_residency(self) -> None:
        """Decide, statically, which region inputs can live in array rows.

        A region input is resident-eligible when its value is DERIVED purely
        from the resident arguments (seeded at the jaxpr invars, propagated
        through eqns whose every Var input is itself derived — closed-over
        constants and literals are call-invariant and never block), its
        in-region consumption pattern admits a pinnable entry pack, and that
        pack's rows fit the empty resident budget of the ResidentSet's
        geometry (an oversize atom silently stays streamed — never an
        error). The warm-skip set then marks host eqns that exist ONLY to
        produce resident-derived values: with every pin warm they are pure
        dead weight and the hybrid executor skips them."""
        rs = self.resident_set
        if rs is None or not self.resident_leaf_idx:
            return
        jaxpr = self.trace.closed.jaxpr
        derived = {jaxpr.invars[i] for i in self.resident_leaf_idx}
        for op in self.trace.ops:
            vars_in = [a for a in op.invars if isinstance(a, Var)]
            if all(v in derived for v in vars_in):
                derived.update(v for v in op.outvars
                               if not isinstance(v, DropVar))
        budget = rs.spec.rows - rs.reserve_rows
        for region in self.regions:
            resident: List[ResidentAtom] = []
            for ai, atom in enumerate(region.in_atoms):
                if not isinstance(atom, Var) or atom not in derived:
                    continue
                ra = _classify_resident(region, ai, atom)
                if ra is None:
                    continue
                rows = rs._rows_for(ra.n_bits, ra.n_words)
                if max(rows.values(), default=0) > budget:
                    continue
                resident.append(ra)
            if resident:
                region.resident = tuple(resident)
                names = tuple(f"in{ra.ai}" for ra in resident)
                region.schedule_resident = region.schedule \
                    .with_operands(*names).with_resident(*names)
                rset = {ra.ai for ra in resident}
                region.donatable_resident = tuple(
                    j for j in region.donatable if j not in rset)
        if not any(r.resident for r in self.regions):
            return
        needed = {v for v in jaxpr.outvars if isinstance(v, Var)}
        skip = set()
        for i in range(len(self.items) - 1, -1, -1):
            kind, payload = self.items[i]
            if kind == "region":
                rset = {ra.ai for ra in payload.resident}
                needed.update(
                    a for j, a in enumerate(payload.in_atoms)
                    if isinstance(a, Var) and j not in rset)
            else:
                outs = [v for v in payload.outvars
                        if not isinstance(v, DropVar)]
                if not any(v in needed for v in outs):
                    skip.add(i)
                else:
                    needed.update(v for v in payload.invars
                                  if isinstance(v, Var))
        self._warm_skip = frozenset(skip)

    def _plan_islands(self) -> List[Tuple[str, Any]]:
        """The items as executed: each region alone, each maximal run of
        consecutive host eqns as one island of (cold eqns, warm eqns), the
        warm list without the eqns a warm resident call skips."""
        steps: List[Tuple[str, Any]] = []
        for i, (kind, payload) in enumerate(self.items):
            if kind == "region":
                steps.append(("region", payload))
                continue
            if not steps or steps[-1][0] != "host":
                steps.append(("host", ([], [])))
            cold, warm = steps[-1][1]
            cold.append(payload)
            if i not in self._warm_skip:
                warm.append(payload)
        return steps

    def _build_resident_pack(self, region: Region, ra: ResidentAtom,
                             value) -> PlanePack:
        """The concrete plane stack a ResidentSet pins for one atom —
        bitwise identical to what the region body would build per call."""
        arr = jnp.asarray(value)
        if ra.kind in ("matmul_rhs", "batched_matmul_rhs"):
            # replay the skipped pass-through chain on the host: these are
            # the eqns between the region input and the dot's rhs
            for ei in ra.chain_eqns:
                op = region.ops[ei]
                oav = aval_of(op.outvars[0])
                if op.name == "convert_element_type":
                    arr = arr.astype(oav.dtype)
                else:
                    arr = arr.reshape(tuple(oav.shape))
            if ra.kind == "batched_matmul_rhs":
                return macro.batched_matmul_rhs_pack(arr, ra.m, ra.n_bits,
                                                     signed=ra.signed)
            return macro.matmul_rhs_pack(arr, ra.m, ra.n_bits,
                                         signed=ra.signed)
        if arr.dtype == jnp.bool_:
            arr = arr.astype(jnp.int32)
        return _pack_program(arr, ra.n_bits, ra.signed)

    # -- execution ----------------------------------------------------------
    def execute(self, *args):
        with jax.profiler.TraceAnnotation("cim.call", fn=self.name):
            return self._execute(args)

    __call__ = execute

    def _execute(self, args):
        leaves = jax.tree_util.tree_leaves(args)
        invars = self.trace.closed.jaxpr.invars
        if len(leaves) != len(invars):
            raise CimOpError(
                f"lowered function takes {len(invars)} array leaves, "
                f"got {len(leaves)}")
        env: Dict[Any, Any] = dict(zip(invars, leaves))
        # a closed-over constant can BE an output (or leak past the invar
        # substitution); seed the env so those reads resolve
        env.update(zip(self.trace.closed.jaxpr.constvars,
                       self.trace.closed.consts))

        # residency: active only with concrete resident leaves — under an
        # outer jit the leaves are Tracers, whose identity is per-trace and
        # whose planes must not be captured in a pin, so the call falls
        # back to the plain streamed path (charged once per outer trace,
        # exactly as before)
        rs = self.resident_set
        if self._registry_rs and self.resident_leaf_idx:
            # registry-backed: re-resolve each call so ECC toggles,
            # clear_resident() and failover spec swaps take effect on the
            # next execution instead of pinning into a stale set
            rs = array_mod.lowering_resident_set(self.spec)
        resident_on = (rs is not None and self.resident_leaf_idx
                       and any(r.resident for r in self.regions)
                       and not any(isinstance(leaves[i], jax.core.Tracer)
                                   for i in self.resident_leaf_idx))
        fp = None
        keep = None
        warm = False
        if resident_on:
            # the fingerprint is PART of the key: one LoweredComputation is
            # shared by every caller with these avals (e.g. identical layers
            # of a stack), and each caller's weights deserve their own pin.
            # The entry keeps strong refs (aux) to the fingerprinted arrays
            # and this computation, so a recycled id() can never alias.
            fp = tuple(id(leaves[i]) for i in self.resident_leaf_idx)
            keep = tuple(leaves[i] for i in self.resident_leaf_idx) + (self,)
            warm = all(
                rs.peek(("lowered", id(self), r.index, ra.ai) + fp, fp)
                for r in self.regions for ra in r.resident)

        for kind, payload in self._steps:
            if kind == "host":
                ops = payload[1] if warm else payload[0]
                if ops:
                    n = len(ops)
                    with jax.profiler.TraceAnnotation("cim.host", eqns=n):
                        for op in ops:
                            self._run_host(op, env)
                    dispatch.count_host_eqns(n)
                continue
            with jax.profiler.TraceAnnotation("cim.region",
                                              region=payload.index):
                rmap = None
                if resident_on and payload.resident:
                    rmap = {}
                    for ra in payload.resident:
                        key = ("lowered", id(self), payload.index,
                               ra.ai) + fp
                        entry = rs.get(key, fingerprint=fp)
                        if entry is None:
                            value = _read_host(env, payload.in_atoms[ra.ai])
                            entry = rs.pin(
                                key,
                                self._build_resident_pack(payload, ra,
                                                          value),
                                fingerprint=fp, aux=keep)
                        rmap[ra.ai] = entry.pack
                self._run_region(payload, env, resident_map=rmap)
        outs = [_read_host(env, v) for v in self.trace.closed.jaxpr.outvars]
        out_tree = jax.tree_util.tree_structure(self.trace.out_shape)
        return jax.tree_util.tree_unflatten(out_tree, outs)

    def _run_host(self, op: TracedOp, env: Dict[Any, Any]) -> None:
        if op.name == "_alias":
            env[op.outvars[0]] = _read_host(env, op.invars[0])
            return
        subfuns, bind_params = op.prim.get_bind_params(op.params)
        in_vals = [_read_host(env, v) for v in op.invars]
        vals = op.prim.bind(*subfuns, *in_vals, **bind_params)
        if not op.prim.multiple_results:
            vals = [vals]
        for var, val in zip(op.outvars, vals):
            if not isinstance(var, DropVar):
                env[var] = val

    def _run_region(self, region: Region, env: Dict[Any, Any],
                    resident_map: Optional[Dict[int, PlanePack]] = None
                    ) -> None:
        """Execute a fused region as ONE jitted XLA program: gather the
        region's input leaves from the host env, invoke (or compile) the
        cached step program, land the unpacked outputs back in the env.

        With `resident_map` (atom index -> pinned PlanePack) the resident
        atoms enter the program AS plane stacks — their raw values are
        never read, their entry packs never rebuilt — under the resident
        schedule and a resident-marked body key, so streamed and resident
        executions of one region never share a compiled program."""
        leaves = tuple(
            resident_map[j] if resident_map and j in resident_map
            else _read_host(env, a)
            for j, a in enumerate(region.in_atoms))
        if resident_map:
            schedule = region.schedule_resident
            body_key = ("region", region.key,
                        ("resident",) + region.resident)
            donatable = region.donatable_resident
            body = self._region_body(region, frozenset(resident_map))
        else:
            schedule = region.schedule
            body_key = ("region", region.key)
            donatable = region.donatable
            body = self._region_body(region)
        # donation only pays (and only passes silently) on accelerators;
        # CPU jit ignores donations with a warning, so skip it there
        donate = donatable \
            if jax.default_backend() in ("gpu", "tpu") else ()
        # the program is cached by structure, not by name: structurally
        # identical regions (of other layers, or of another lowered function)
        # share the program and the name of the one that compiled it
        outs = macro.run_schedule_program(
            schedule, body, leaves,
            body_key=body_key, backend=self.backend,
            spec=self.spec, mesh=self.mesh, donate=donate,
            name=f"cim_{self.name}_r{region.index}")
        for var, val in zip(region.unpack_vars, outs):
            env[var] = val

    def _region_body(self, region: Region,
                     resident_ais: frozenset = frozenset()):
        """The traceable region computation `run_schedule_program` compiles:
        the per-eqn execution loop over the program's shared cursor."""
        resident_kinds = {ra.ai: ra for ra in region.resident
                          if ra.ai in resident_ais}
        # eqns replayed into the pinned pack at pin time: dead in the body
        skip_eqns = frozenset(ei for ra in resident_kinds.values()
                              for ei in ra.chain_eqns)

        def body(cur, *leaves):
            chain = macro.ChainExecutor.from_cursor(cur)
            var_env: Dict[Any, Any] = {}
            const_env: Dict[int, Any] = {}
            resident_matmul: Dict[Any, PlanePack] = {}
            penv: Dict[Any, PlanePack] = {}
            for j, (atom, leaf) in enumerate(zip(region.in_atoms, leaves)):
                ra = resident_kinds.get(j)
                if ra is not None:
                    if ra.kind in ("matmul_rhs", "batched_matmul_rhs"):
                        # keyed at the END of the pass-through chain — the
                        # var the dot handler actually consumes; the reuse
                        # charge lands inside _matmul_with
                        fvar = region.ops[ra.chain_eqns[-1]].outvars[0] \
                            if ra.chain_eqns else atom
                        resident_matmul[fvar] = leaf
                    else:
                        penv[atom] = leaf     # pre-seeded entry pack
                        cur.charge_resident(leaf.n_bits, leaf.n_words)
                elif isinstance(atom, ConstVal):
                    const_env[id(atom)] = leaf
                else:
                    var_env[atom] = leaf

            def read(atom):
                if isinstance(atom, Literal):
                    return jnp.asarray(atom.val, dtype=atom.aval.dtype)
                if isinstance(atom, ConstVal):
                    return const_env[id(atom)]
                return var_env[atom]

            def getp(atom, shape) -> PlanePack:
                """Operand as a PlanePack of logical `shape` (region entry
                pack for external values — each packed ONCE per region —
                with scalar fanout staying in the packed domain)."""
                if isinstance(atom, Var) and atom in penv:
                    p = penv[atom]
                    if p.shape != tuple(shape):
                        p = _broadcast_pack(p, tuple(shape))
                    return p
                aval = aval_of(atom)
                arr = jnp.asarray(read(atom))
                if arr.dtype == jnp.bool_:
                    arr = arr.astype(jnp.int32)
                if tuple(arr.shape) != tuple(shape):
                    arr = jnp.broadcast_to(arr, tuple(shape))
                p = PlanePack.pack(arr, dtype_bits(aval.dtype),
                                   signed=dtype_signed(aval.dtype))
                # a freshly built entry pack is a STREAMED operand load:
                # its planes are driven into rows before the first access
                # (resident atoms never reach here — they are pre-seeded)
                cur.charge_load(p.n_bits, p.n_words)
                if isinstance(atom, Var) and \
                        tuple(shape) == tuple(aval.shape):
                    penv[atom] = p    # entry pack: reused by later consumers
                return p

            def geti(atom) -> jax.Array:
                """Operand as an integer array (the dot_general layout
                rebuild — the one declared in-region materialization)."""
                if isinstance(atom, Var) and atom in penv:
                    aval = aval_of(atom)
                    return penv[atom].unpack().astype(aval.dtype)
                return jnp.asarray(read(atom))

            for ei, op in enumerate(region.ops):
                if ei in skip_eqns:
                    continue
                out_aval = aval_of(op.outvars[0])
                shape = tuple(out_aval.shape)
                name = op.name
                if name in ("add", "sub", "and", "or", "xor"):
                    pa = getp(op.invars[0], shape)
                    pb = getp(op.invars[1], shape)
                    res = chain.execute(pa, pb, (name,))[name]
                elif name in CMP_PRIMS:
                    base, complement = CMP_PRIMS[name]
                    pa = getp(op.invars[0], shape)
                    pb = getp(op.invars[1], shape)
                    res = chain.execute(pa, pb, (base,))[base]
                    if complement:
                        res = _complement(res)
                elif name == "min":
                    res = chain.minimum(getp(op.invars[0], shape),
                                        getp(op.invars[1], shape))
                elif name == "max":
                    res = chain.maximum(getp(op.invars[0], shape),
                                        getp(op.invars[1], shape))
                elif name == "neg":
                    res = chain.neg(getp(op.invars[0], shape))
                elif name == "abs":
                    res = chain.abs_(getp(op.invars[0], shape))
                elif name == "mul":
                    res = chain.multiply(getp(op.invars[0], shape),
                                         getp(op.invars[1], shape))
                elif name == "population_count":
                    res = chain.popcount(getp(op.invars[0], shape))
                elif name == "reduce_sum":
                    src_shape = tuple(aval_of(op.invars[0]).shape)
                    res = chain.reduce_sum(getp(op.invars[0], src_shape))
                elif name == "dot_general":
                    rb = resident_matmul.get(op.invars[1]) \
                        if isinstance(op.invars[1], Var) else None
                    nb = len(op.params["dimension_numbers"][1][0])
                    mm = chain.batched_matmul if nb else chain.matmul
                    res = mm(geti(op.invars[0]),
                             None if rb is not None
                             else geti(op.invars[1]), op.n_bits,
                             signed=dtype_signed(
                                 aval_of(op.invars[0]).dtype),
                             b_pack=rb)
                elif name == "convert_element_type":
                    src_shape = tuple(aval_of(op.invars[0]).shape)
                    res = getp(op.invars[0], src_shape)
                elif name == "reshape":
                    src_shape = tuple(aval_of(op.invars[0]).shape)
                    res = getp(op.invars[0], src_shape)
                elif name == "not":
                    res = _complement(getp(op.invars[0], shape))
                elif name == "select_n":
                    pred = getp(op.invars[0], shape)
                    x = getp(op.invars[1], shape)
                    y = getp(op.invars[2], shape)
                    res = macro.select(pred, y, x)  # pred ? cases[1] : cases[0]
                elif name == "broadcast_in_dim":
                    src_shape = tuple(aval_of(op.invars[0]).shape)
                    res = _broadcast_pack(getp(op.invars[0], src_shape),
                                          shape)
                else:                             # pragma: no cover
                    raise CimOpError(f"region executor missing op {name!r}")
                if not isinstance(op.outvars[0], DropVar):
                    penv[op.outvars[0]] = _finish(res, out_aval)

            return tuple(penv[var].unpack().astype(aval_of(var).dtype)
                         for var in region.unpack_vars)

        return body

    # -- reporting ----------------------------------------------------------
    @property
    def accesses(self) -> int:
        """Planned (== executed, unbanked) ADRA accesses per call."""
        return sum(r.accesses for r in self.regions)

    @property
    def eligible_eqns(self) -> int:
        return sum(len(r.ops) for r in self.regions)

    @property
    def host_eqns(self) -> int:
        return sum(1 for kind, _ in self.items if kind == "host")

    def describe(self) -> str:
        plan = self.offload_plan
        lines = [f"lowered: {len(self.regions)} CiM region(s), "
                 f"{self.host_eqns} host eqn(s), "
                 f"{self.accesses} planned accesses "
                 f"[policy={plan.policy}, {plan.demoted_eqns} demoted, "
                 f"{plan.fused_losses} kept fused despite loss]"]
        for v in plan.verdicts:
            if v.index in plan.demoted:
                lines.append(f"  demoted eqn#{v.index} {v.name} "
                             f"({v.accesses} accesses): {v.reason} "
                             f"(margin {100 * v.margin:+.1f}%)")
        for r in self.regions:
            segs = ", ".join(f"{name}:{n}" for name, n in
                             (r.schedule.segments or ()))
            lines.append(f"  {r.name}: {len(r.ops)} eqns fused -> "
                         f"{r.accesses} accesses [{segs}]")
        return "\n".join(lines)


#: per-function bound on cached signature traces — a long-lived server fed
#: ever-varying shapes must not grow a LoweredFunction without limit (the
#: same growth class the dispatch schedule cache bounds one layer down)
SIGNATURE_CACHE_CAPACITY = 128


class LoweredFunction:
    """`lower(fn)`: traces lazily per argument signature (like jit) and
    executes the hybrid CiM/host program. The signature cache is a bounded
    LRU (SIGNATURE_CACHE_CAPACITY); an evicted signature simply retraces."""

    def __init__(self, fn, backend: Optional[str] = None,
                 spec: Optional[ArraySpec] = None, mesh=None,
                 resident_argnums: Tuple[int, ...] = (),
                 resident_set=None, policy: Optional[str] = None,
                 device=None, name: Optional[str] = None):
        self.fn = fn
        # an identifier: it names the region programs (`cim_<name>_r<i>`)
        self.name = re.sub(r"\W+", "_", name or getattr(fn, "__name__", "")
                           ).strip("_") or "fn"
        self.backend = backend
        self.spec = spec
        self.mesh = mesh
        self.resident_argnums = tuple(resident_argnums)
        self.resident_set = resident_set
        self.policy = cost_mod.normalize_policy(policy)
        self.device = device
        # resident_set=None means "the registry set for `spec`", resolved
        # PER EXECUTION by LoweredComputation — never captured here: the
        # registry set is replaced by clear_resident()/set_resident_ecc()/
        # failover, and a captured reference would keep pinning into a
        # stale (e.g. unprotected) set for the life of the layer cache
        self._cache: "OrderedDict[Any, LoweredComputation]" = OrderedDict()

    def _resident_leaf_idx(self, args) -> Tuple[int, ...]:
        """Flat leaf indices of the resident argnums (the positions
        `execute` fingerprints and the residency planner seeds from)."""
        if not self.resident_argnums:
            return ()
        spans = []
        start = 0
        for a in args:
            n = len(jax.tree_util.tree_leaves(a))
            spans.append((start, start + n))
            start += n
        idx: List[int] = []
        for an in self.resident_argnums:
            if an < len(spans):
                idx.extend(range(*spans[an]))
        return tuple(idx)

    def trace(self, *args) -> LoweredComputation:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        key = (treedef, tuple(
            (jnp.shape(x), str(jnp.result_type(x))) for x in leaves))
        comp = self._cache.get(key)
        if comp is None:
            comp = LoweredComputation(
                trace_mod.trace(self.fn, *args), backend=self.backend,
                spec=self.spec, mesh=self.mesh,
                resident_leaf_idx=self._resident_leaf_idx(args),
                resident_set=self.resident_set, policy=self.policy,
                device=self.device, name=self.name)
            self._cache[key] = comp
            while len(self._cache) > SIGNATURE_CACHE_CAPACITY:
                self._cache.popitem(last=False)
        else:
            self._cache.move_to_end(key)
        return comp

    def __call__(self, *args):
        return self.trace(*args).execute(*args)


def lower(fn, backend: Optional[str] = None,
          spec: Optional[ArraySpec] = None, mesh=None,
          resident_argnums: Tuple[int, ...] = (),
          resident_set=None, policy: Optional[str] = None,
          device=None, name: Optional[str] = None) -> LoweredFunction:
    """Compile `fn` into a hybrid CiM/host callable (see module docstring).

    backend : CiM backend name for the fused regions (registry default
              when None).
    spec    : optional banked ArraySpec — region accesses tile over banks
              through the dispatch layer and the ledger charges per
              (device, bank) activations.
    mesh    : optional device mesh forwarded to the tiling dispatcher.
    resident_argnums : argument positions whose (pure) derivatives may be
              pinned in the resident region: region inputs derived solely
              from these arguments skip their per-call entry pack once
              pinned, and host eqns that only feed pinned values are
              skipped on warm passes. Identity-fingerprinted — pass the
              SAME weight arrays each call to stay warm.
    resident_set : the ResidentSet to pin into (the process-wide registry
              set for `spec` when omitted).
    policy  : offload policy (repro.cim.cost): "edp" (default, alias
              "cost") lowers an eqn only when its projected CiM EDP beats
              the near-memory baseline; "latency" compares against the
              DeviceSpec host roofline; "always" reproduces the
              pre-cost-model behavior bit-exactly; "never" demotes all.
    device  : DeviceSpec for the host side of the comparison
              (cost.DEFAULT_DEVICE — a v5e chip — when None).
    name    : what its `cim.call` spans and region programs are called
              (`fn.__name__` when None; non-word characters become `_`).
    """
    return LoweredFunction(fn, backend=backend, spec=spec, mesh=mesh,
                           resident_argnums=resident_argnums,
                           resident_set=resident_set, policy=policy,
                           device=device, name=name)
