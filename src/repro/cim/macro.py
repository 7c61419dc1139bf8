"""Macro-op executors: multi-access CiM arithmetic over the single-access
engine, compiled to ONE jitted XLA program per schedule.

Every macro here executes a `planner.Schedule` through a cursor that allows
exactly the planned accesses (same order, same op-sets) and nothing else.
The cursor has two modes:

  * eager (charges=None): each step is one `engine.execute` /
    `dispatch.execute_tiled` call charging the ledger directly — tens of
    host round trips per macro, kept for direct cursor users and tests.
  * traced (charges=list): each step is the side-effect-free
    `execute_traced` form and appends its ledger charge to a
    charge-from-plan record instead of mutating anything.

`run_schedule_program` uses the traced mode to compile a whole schedule —
every access plus all the packed-domain peripherals between them (plane
shifts, truncations, selects, row-buffer strides) — into a single `jax.jit`
program, cached in the dispatch layer's bounded LRU keyed on schedule
structure. A warm macro is ONE XLA dispatch; the recorded PlannedCharges
replay into the ledger per invocation, so `ledger accesses ==
schedule.accesses` still holds by construction. ADRA step sequences are
width-heterogeneous (bit growth between accesses: partial products widen,
tree levels deepen), so the step program is an unrolled trace rather than a
`lax.scan` — XLA pipelines the unrolled chain and aliases the accumulator
buffers internally; scan would require shape-stable carries no ADRA
schedule has.

Operands, partial products, accumulators and tree levels all stay in the
PlanePack packed domain; the only integer codec entries are the caller's
own pack() at entry and unpack() at exit.

Macros:

  multiply   — shift-and-add; signed multipliers subtract the MSB partial
               product (single-access sub, the paper's headline op)
  abs_/relu  — sub-chain predicate + zero-cost peripheral select
  minimum/maximum — lt/gt predicate + select, one access each
  popcount   — pairwise plane tree, n-1 add accesses
  reduce_sum — log-stride tree reduction with row-buffer shifts
  dot/matmul — int x int -> wide-int contraction: one multiply over a
               broadcast [M, K_pad, N] layout + a stride-N reduction; the
               access count depends only on the bit width and K (word
               parallelism), never on M or N. In a compiled unbanked
               program the reduction folds in halves and computes only
               the words its result keeps; the ledger still charges each
               add at the full row
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dispatch, engine, opset, planner
from .accounting import LEDGER, PlannedCharges
from .array import ArraySpec
from .backends import get_backend
from .opset import CimOpError
from .planepack import PlanePack


class ScheduleCursor:
    """Executes a Schedule one access at a time, refusing to deviate.

    This is the accounting guarantee: a macro CANNOT issue an access its
    plan does not contain, so ledger accesses == schedule.accesses holds by
    construction, not by convention. With an ArraySpec the cursor routes
    every access through the banked tiling dispatcher instead of the
    infinite-array engine — each planned step then costs `plan.n_tiles`
    bank activations and the guarantee becomes ledger accesses ==
    schedule.placed_accesses. A mesh additionally spreads the tiles over
    its "data" axis via shard_map.

    With `charges` (a list) the cursor is in TRACED mode: accesses run
    through the side-effect-free `execute_traced` forms, the ledger is
    never touched, and every planned charge is appended to `charges` — the
    charge-from-plan record `run_schedule_program` replays per invocation
    of the compiled step program.

    `fold_steps` and `shift_steps` count the tree-reduction accesses the
    cursor ran as a halving fold (`_fold_reduce`) and as row-buffer shifts
    (`_reduce_with`); a compiled program reports them per invocation
    through `dispatch.cache_stats()`.
    """

    def __init__(self, schedule: planner.Schedule,
                 backend: Optional[str] = None,
                 spec: Optional[ArraySpec] = None,
                 mesh=None, charges: Optional[list] = None):
        self.schedule = schedule
        self.backend = backend
        self.spec = spec
        self.mesh = mesh
        self.charges = charges
        self._i = 0
        self.fold_steps = 0
        self.shift_steps = 0

    def step(self) -> planner.Step:
        if self._i >= len(self.schedule.steps):
            raise CimOpError(
                f"{self.schedule.macro}: executor exceeded its planned "
                f"{self.schedule.accesses} accesses")
        return self.schedule.steps[self._i]

    def execute(self, a: PlanePack, b: PlanePack, ops: Sequence[str],
                charge_words: Optional[int] = None) -> engine.Outputs:
        """One planned access. `charge_words` (traced unbanked cursors
        only) charges the modelled access's words where the operands hold
        fewer: the matmul's halving fold computes only the words its exit
        keeps, while the array activates the full row."""
        step = self.step()
        if tuple(ops) != step.ops:
            raise CimOpError(
                f"{self.schedule.macro}: access {self._i} executes {ops!r} "
                f"but the plan says {step.ops!r}")
        if charge_words is not None and not self.folds:
            raise CimOpError(
                f"{self.schedule.macro}: charge_words needs a traced "
                f"unbanked cursor")
        self._i += 1
        if self.charges is not None:
            if self.spec is None:
                return engine.execute_traced(a, b, step.ops,
                                             backend=self.backend,
                                             charges=self.charges,
                                             n_words=charge_words)
            return dispatch.execute_tiled_traced(
                a, b, step.ops, spec=self.spec, backend=self.backend,
                mesh=self.mesh, charges=self.charges)
        if self.spec is None:
            return engine.execute(a, b, step.ops, backend=self.backend)
        return dispatch.execute_tiled(a, b, step.ops, spec=self.spec,
                                      backend=self.backend, mesh=self.mesh)

    @property
    def folds(self) -> bool:
        """Whether a matmul's tree reduction may run as a halving fold:
        traced (every access a charge-from-plan record, so the charged words
        need not be the computed ones) and unbanked (a banked reduction's
        inter-bank charge depends on each shift's stride against the tile
        boundaries). Eager cursors keep the shifts, where the fault overlay
        corrupts the streamed operands at their full width."""
        return self.charges is not None and self.spec is None

    def charge_reduction(self, words32: float) -> None:
        """Inter-bank reduction traffic: charged directly in eager mode,
        recorded into the charge-from-plan record in traced mode."""
        if self.charges is not None:
            self.charges.append(("reduction", float(words32)))
        else:
            LEDGER.charge_reduction(words32)

    def charge_load(self, n_bits: int, n_words: int) -> None:
        """Operand-load row-writes for one STREAMED entry pack built inside
        this schedule (one load access per tile it lands on). Resident
        operands never reach this — they charge `charge_resident` instead."""
        n_tiles = self.spec.plan(n_words).n_tiles if self.spec else 1
        if self.charges is not None:
            self.charges.append(("load", n_bits, n_words, n_tiles))
        else:
            LEDGER.charge_load(n_bits, n_words, n_tiles=n_tiles)

    def charge_resident(self, n_bits: int, n_words: int) -> None:
        """One resident-operand reuse: entry pack (and its loads) skipped."""
        if self.charges is not None:
            self.charges.append(("resident", n_bits, n_words))
        else:
            LEDGER.charge_resident_reuse(n_bits, n_words)

    def remaining(self) -> Tuple[planner.Step, ...]:
        return self.schedule.steps[self._i:]

    def finish(self) -> None:
        if self._i != len(self.schedule.steps):
            raise CimOpError(
                f"{self.schedule.macro}: executed {self._i} of "
                f"{self.schedule.accesses} planned accesses")


# ---------------------------------------------------------------------------
# whole-schedule step programs: one jitted XLA dispatch per macro/region
# ---------------------------------------------------------------------------


class CompiledSchedule:
    """A jitted whole-schedule program plus its charge-from-plan record.

    Calling it replays the recorded ledger charges (computed once, at trace
    time, from the cursor-checked plan) and invokes the compiled program —
    ONE XLA dispatch for the entire schedule. `reduce_steps` holds the
    (fold, shift) tree-reduction accesses the trace ran, counted per
    invocation like the dispatch."""

    __slots__ = ("fn", "charges", "reduce_steps")

    def __init__(self, fn, charges: PlannedCharges,
                 reduce_steps: Tuple[int, int] = (0, 0)):
        self.fn = fn
        self.charges = charges
        self.reduce_steps = reduce_steps

    def __call__(self, *leaves):
        # invoke first, account after: a failed invocation must not leave
        # the ledger charged (or the dispatch counter bumped) for an
        # execution that never happened
        out = self.fn(*leaves)
        self.account()
        return out

    def account(self) -> None:
        """The per-invocation bookkeeping: charges, dispatch, reductions."""
        self.charges.replay()
        dispatch.count_dispatch()
        dispatch.count_reduce_steps(*self.reduce_steps)


def aval_sig(aval) -> Tuple:
    """Cache-key signature of one abstract value: shape, dtype and
    weak_type — anything jit would retrace on must be in OUR program-cache
    keys, or a cache hit could replay charges recorded from a different
    trace. The ONE definition of that discipline; the lowering compiler's
    region keys use it too."""
    return (tuple(aval.shape), str(aval.dtype),
            bool(getattr(aval, "weak_type", False)))


def _leaf_sig(x):
    """aval_sig of a concrete (or traced) input leaf."""
    try:
        return aval_sig(jax.typeof(x))
    except Exception:
        return aval_sig(jnp.asarray(x))


def run_schedule_program(schedule: planner.Schedule, body, operands,
                         body_key=(), backend: Optional[str] = None,
                         spec: Optional[ArraySpec] = None, mesh=None,
                         donate: Tuple[int, ...] = (), name: str = "fn"):
    """Execute `body(cursor, *operands)` as ONE jitted XLA program.

    The whole schedule — every planned access plus the zero-cost
    packed-domain peripherals between them — is traced once into a single
    `jax.jit` program (unrolled: ADRA step sequences are width-
    heterogeneous, see module docstring) and cached in the dispatch layer's
    bounded LRU, keyed on the schedule structure, the body identity
    (`body_key`), operand signatures, backend, banked geometry and mesh. A
    repeated macro or fused region therefore hits end-to-end: zero retrace,
    one dispatch, and the PlannedCharges recorded at trace time replayed
    into the ledger — accesses == schedule.accesses, unbanked or banked,
    exactly as the eager cursor charged.

    `donate` names operand leaf positions whose buffers the program may
    reuse for an output of the same shape and dtype (jit donate_argnums);
    callers must only donate buffers that are dead after the call.

    `name` names the jitted function, so the program appears as
    `jit_<name>` in a profile. It is not part of the key: a cache hit
    runs the program under the name it was compiled with.

    Inside the program, named scopes mark the work a profile should tell
    apart: `cim.layout` (a matmul's broadcast operand layout),
    `cim.multiply` and `cim.reduce` (the shift-and-add multiply and the
    tree reduction, plane and element shifts included), `cim.kernel` (the
    fused Pallas call) and `cim.kernel_pad` (its own pad and slice).

    Residency note: a cached program keeps its body closure (for a region:
    the Region and any closed-over ConstVal constants) alive until LRU
    eviction — that is what makes eviction-then-recompile possible. The
    bounded capacity (set_schedule_cache_capacity / REPRO_CIM_CACHE_CAPACITY)
    is the memory ceiling; long-lived servers that reload weights should
    size it accordingly.
    """
    bk_name = get_backend(backend).name
    leaves, treedef = jax.tree_util.tree_flatten(operands)
    key = ("step-program", schedule, tuple(body_key), treedef,
           tuple(_leaf_sig(x) for x in leaves), bk_name, spec, mesh,
           tuple(donate))
    prog = dispatch.program_cache_get(key)
    if prog is not None:
        return prog(*leaves)

    # operand-load charges are the BODY's responsibility (cur.charge_load /
    # charge_resident at the point a streamed entry pack is built), never
    # implied by an operand's type: a top-level PlanePack may already live
    # in rows, and eager-cursor execution must charge identically
    charges: list = []
    reduce_steps = [0, 0]

    def fn(*flat):
        args = jax.tree_util.tree_unflatten(treedef, list(flat))
        cur = ScheduleCursor(schedule, bk_name, spec=spec, mesh=mesh,
                             charges=charges)
        out = body(cur, *args)
        cur.finish()
        reduce_steps[:] = [cur.fold_steps, cur.shift_steps]
        return out

    fn.__name__ = name
    jitted = jax.jit(fn, donate_argnums=tuple(donate))
    out = jitted(*leaves)       # first call traces: `charges` fills here
    planned = PlannedCharges(tuple(charges))
    if planned.accesses != schedule.accesses:   # pragma: no cover
        raise CimOpError(
            f"{schedule.macro}: traced {planned.accesses} accesses but the "
            f"plan has {schedule.accesses}")
    prog = CompiledSchedule(jitted, planned, tuple(reduce_steps))
    dispatch.program_cache_put(key, prog)
    prog.account()
    return out


def _place(sched: planner.Schedule, spec: Optional[ArraySpec],
           n_words: int) -> planner.Schedule:
    """Pin a schedule to the banked geometry (when given) — the single spot
    where placement meets compilation."""
    return sched.placed(spec, n_words) if spec is not None else sched


# ---------------------------------------------------------------------------
# peripheral select (zero accesses)
# ---------------------------------------------------------------------------


def select(pred: PlanePack, x: PlanePack, y: PlanePack) -> PlanePack:
    """Per-word mux: pred ? x : y, as predicated writeback in the periphery.

    The predicate is a 1-plane bitmap (an engine lt/eq/gt output); selection
    gates which operand's planes reach the row buffer — no array access.
    """
    if pred.planes.shape[0] != 1:
        raise CimOpError("select predicate must be a 1-plane bitmap")
    if x.signed != y.signed:
        n = max(x.n_bits, y.n_bits) + 1   # room so both read as signed
        x, y = x.extend_to(n).as_signed(True), y.extend_to(n).as_signed(True)
    x, y = x.align(y)
    mask = pred.planes[0]
    planes = (x.planes & mask) | (y.planes & ~mask)
    return PlanePack(planes=planes, n_bits=x.n_bits,
                     signed=x.signed, shape=x.shape)


def _plane_mask(bitmap: jax.Array, n_bits: int, like: PlanePack) -> PlanePack:
    """One multiplier-bit bitmap replicated across n_bits planes (the row
    driver asserting the same enable on every plane — free wiring)."""
    planes = jnp.broadcast_to(bitmap[None], (n_bits,) + bitmap.shape)
    return PlanePack(planes=planes, n_bits=n_bits, signed=True,
                     shape=like.shape)


# ---------------------------------------------------------------------------
# multiply
# ---------------------------------------------------------------------------


@jax.named_scope("cim.multiply")
def _multiply_with(cur: ScheduleCursor, a: PlanePack,
                   b: PlanePack) -> PlanePack:
    """Shift-and-add over a cursor (shared by multiply and matmul)."""
    w = a.n_bits + b.n_bits
    a_ext = a.extend_to(w).as_signed(True)
    acc: Optional[PlanePack] = None
    for i in range(b.n_bits):
        last_signed = b.signed and i == b.n_bits - 1
        pp = cur.execute(a_ext, _plane_mask(b.planes[i], w, a), ("and",))
        # AND of a sign-extended word against a replicated enable bit is a
        # valid two's-complement word (a_ext or 0); shift = weight 2^i,
        # truncation keeps the arithmetic modulo 2^w
        shifted = pp["and"].as_signed(True).truncate_to(w - i).shift_up(i)
        if acc is None:
            if last_signed:            # 1-bit signed multiplier: b in {0,-1}
                zero = PlanePack.zeros_like(shifted)
                acc = cur.execute(zero, shifted, ("sub",))["sub"]
            else:
                acc = shifted
        else:
            op = "sub" if last_signed else "add"
            acc = cur.execute(acc, shifted, (op,))[op]
        acc = acc.truncate_to(w)
    return acc.as_signed(a.signed or b.signed)


def multiply(a: PlanePack, b: PlanePack,
             backend: Optional[str] = None,
             spec: Optional[ArraySpec] = None, mesh=None) -> PlanePack:
    """Exact product, (n_a + n_b)-plane result, 2*n_b - 1 accesses (times
    the tile count when placed on a banked `spec`) — compiled to one XLA
    dispatch."""
    if a.shape != b.shape:
        raise CimOpError(f"operand shapes differ: {a.shape} vs {b.shape}")
    sched = _place(planner.plan_multiply(a.n_bits, b.n_bits,
                                         signed_b=b.signed), spec, a.n_words)
    return run_schedule_program(sched, _multiply_with, (a, b),
                                body_key=("multiply",), backend=backend,
                                spec=spec, mesh=mesh)


# ---------------------------------------------------------------------------
# select-based macros: abs / relu / min / max
# ---------------------------------------------------------------------------


def _abs_with(cur: ScheduleCursor, a: PlanePack) -> PlanePack:
    zero = PlanePack.zeros_like(a)
    out = cur.execute(zero, a, ("sub", "lt"))
    return select(out["lt"], a, out["sub"])


def _relu_with(cur: ScheduleCursor, a: PlanePack) -> PlanePack:
    zero = PlanePack.zeros_like(a)
    out = cur.execute(a, zero, ("gt",))
    return select(out["gt"], a, zero)


def _minimum_with(cur: ScheduleCursor, a: PlanePack,
                  b: PlanePack) -> PlanePack:
    out = cur.execute(a, b, ("lt",))
    return select(out["lt"], a, b)


def _maximum_with(cur: ScheduleCursor, a: PlanePack,
                  b: PlanePack) -> PlanePack:
    out = cur.execute(a, b, ("gt",))
    return select(out["gt"], a, b)


def abs_(a: PlanePack, backend: Optional[str] = None,
         spec: Optional[ArraySpec] = None, mesh=None) -> PlanePack:
    """|a| in one access: (0 - a, 0 < a) together, then select a vs -a.
    Result is (n+1)-plane so abs(INT_MIN) is exact."""
    sched = _place(planner.plan_abs(a.n_bits), spec, a.n_words)
    return run_schedule_program(sched, _abs_with, (a,), body_key=("abs",),
                                backend=backend, spec=spec, mesh=mesh)


def relu(a: PlanePack, backend: Optional[str] = None,
         spec: Optional[ArraySpec] = None, mesh=None) -> PlanePack:
    """max(a, 0) in one access: the a > 0 predicate gates the writeback."""
    sched = _place(planner.plan_relu(a.n_bits), spec, a.n_words)
    return run_schedule_program(sched, _relu_with, (a,), body_key=("relu",),
                                backend=backend, spec=spec, mesh=mesh)


def minimum(a: PlanePack, b: PlanePack,
            backend: Optional[str] = None,
            spec: Optional[ArraySpec] = None, mesh=None) -> PlanePack:
    sched = _place(planner.plan_minimum(max(a.n_bits, b.n_bits)), spec,
                   a.n_words)
    return run_schedule_program(sched, _minimum_with, (a, b),
                                body_key=("minimum",), backend=backend,
                                spec=spec, mesh=mesh)


def maximum(a: PlanePack, b: PlanePack,
            backend: Optional[str] = None,
            spec: Optional[ArraySpec] = None, mesh=None) -> PlanePack:
    sched = _place(planner.plan_maximum(max(a.n_bits, b.n_bits)), spec,
                   a.n_words)
    return run_schedule_program(sched, _maximum_with, (a, b),
                                body_key=("maximum",), backend=backend,
                                spec=spec, mesh=mesh)


# ---------------------------------------------------------------------------
# popcount / reductions
# ---------------------------------------------------------------------------


def _popcount_with(cur: ScheduleCursor, a: PlanePack) -> PlanePack:
    level = [PlanePack(planes=a.planes[i:i + 1], n_bits=1, signed=False,
                       shape=a.shape)
             for i in range(a.n_bits)]
    while len(level) > 1:
        nxt = [cur.execute(level[j], level[j + 1], ("add",))["add"]
               for j in range(0, len(level) - 1, 2)]
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]


def popcount(a: PlanePack, backend: Optional[str] = None,
             spec: Optional[ArraySpec] = None, mesh=None) -> PlanePack:
    """Set bits of each word's n-bit two's-complement pattern: pairwise
    plane tree, n - 1 add accesses."""
    sched = _place(planner.plan_popcount(a.n_bits), spec, a.n_words)
    return run_schedule_program(sched, _popcount_with, (a,),
                                body_key=("popcount",), backend=backend,
                                spec=spec, mesh=mesh)


@jax.named_scope("cim.reduce")
def _reduce_with(cur: ScheduleCursor, acc: PlanePack,
                 n_steps: Optional[int] = None) -> PlanePack:
    """Log-stride reduction: each planned step shifts the row buffer by its
    stride and adds, so element 0 of each segment accumulates the segment
    sum; exactness relies on the pack's zero padding past the last word.

    `n_steps` bounds the walk to the next n_steps planned steps — required
    when the cursor belongs to a fused region schedule that continues past
    this reduction; None consumes everything remaining (the standalone
    reduce/matmul cursors, whose plans end with the reduction).

    On a banked cursor the shift moves words BETWEEN tiles whenever the
    stride reaches across a tile boundary — that movement is the inter-bank
    reduction traffic the ledger charges (fraction of words crossing scales
    with stride/tile_words, capped at all of them).

    A matmul's reduction on a traced unbanked cursor folds in halves
    instead (`_fold_reduce`)."""
    if not acc.signed:
        acc = acc.extend_to(acc.n_bits + 1).as_signed(True)
    steps = cur.remaining()
    if n_steps is not None:
        steps = steps[:n_steps]
    for step in steps:
        if cur.spec is not None and step.stride:
            plan = cur.spec.plan(acc.n_words)
            if plan.n_tiles > 1:
                frac = min(1.0, step.stride / plan.tile_words)
                cur.charge_reduction(
                    acc.n_words * frac * acc.n_bits / 32.0)
        shifted = acc.shift_elements(step.stride)
        acc = cur.execute(acc, shifted, ("add",))["add"]
        cur.shift_steps += 1
    return acc


@jax.named_scope("cim.reduce")
def _fold_reduce(cur: ScheduleCursor, prod: PlanePack) -> PlanePack:
    """A matmul's log2(K_pad) planned adds over the [M', K_pad, N] product
    as a halving fold: each level adds the upper half of every row's k
    range onto the lower, acc[:, :H] + acc[:, H:2H] for H = K_pad/2, ...,
    1, and goes on with the compact [M', H, N] sum. Returns the
    [M', K_rem, N] pack whose k = 0 slice is the contraction (K_rem = 1
    where every level folded).

    A level folds while H * N is a whole number of 32-element lane words,
    so each half is a plain slice of the plane stack; the levels below
    that (N not a multiple of 32) shift the row buffer as `_reduce_with`
    does, on the compact pack, with strides N, 2N, ... The sum is exact in
    two's complement in any order, and the j-th add sees the same plane
    widths either way, so the result equals the shift path's bit for bit.
    Each add charges the modelled access — the full M' * K_pad * N row the
    array activates — not the words the emulation computes: the ledger is
    identical to the shift path's."""
    m, k, n = prod.shape
    full = prod.n_words
    acc = prod if prod.signed \
        else prod.extend_to(prod.n_bits + 1).as_signed(True)
    while k > 1 and (k // 2 * n) % 32 == 0:
        k //= 2
        lo, hi = (PlanePack(planes=half, n_bits=acc.n_bits, signed=True,
                            shape=(m, k, n))
                  for half in _fold_halves(acc.planes, m, k * n // 32))
        acc = cur.execute(lo, hi, ("add",), charge_words=full)["add"]
        cur.fold_steps += 1
    stride = n
    while k > 1:
        k //= 2
        shifted = acc.shift_elements(stride)
        acc = cur.execute(acc, shifted, ("add",), charge_words=full)["add"]
        cur.shift_steps += 1
        stride *= 2
    return acc


#: a fold level slices the two halves of each row out of the plane stack
#: while there are at most this many rows; past it (batched attention's
#: slots x heads rows of short runs) one reshape splits every row at once.
#: Both are plain copies. The bound keeps the level's op count, and so the
#: TPU compile time, flat: hundreds of slices compile slowly, and so does
#: a reshape of a long stack (a region's MLP has at most a few rows)
_FOLD_SLICE_ROWS = 32


def _fold_halves(planes: jax.Array, m: int,
                 x: int) -> Tuple[jax.Array, jax.Array]:
    """The lower and upper halves of each of the `m` rows of a plane stack
    whose rows are 2 * x lane words each, as two [n, m * x] stacks."""
    if m <= _FOLD_SLICE_ROWS:
        return tuple(
            jnp.concatenate([planes[:, (2 * r + i) * x:(2 * r + i + 1) * x]
                             for r in range(m)], axis=1)
            for i in (0, 1))
    n = planes.shape[0]
    rows = planes.reshape(n * m, 2 * x)
    return tuple(rows[:, i * x:(i + 1) * x].reshape(n, m * x)
                 for i in (0, 1))


def _k0_slice(acc: PlanePack, shape: Tuple[int, ...]) -> PlanePack:
    """The k = 0 slice of an [M', K, N] reduction result as a pack of
    `shape` (M' * N words): flat(m, 0, n) = m * K * N + n. A gather where
    K > 1; where K = 1 the pack already is that slice."""
    m, k, n = acc.shape
    if k == 1:
        return dataclasses.replace(acc, shape=tuple(shape))
    idx = np.arange(m)[:, None] * (k * n) + np.arange(n)[None, :]
    return acc.take_words(idx.reshape(-1), shape)


def _shift_reduce(cur: ScheduleCursor, prod: PlanePack,
                  shape: Tuple[int, ...]) -> PlanePack:
    """The matmul reduction as row-buffer shifts over the whole product
    (banked and eager cursors), then the k = 0 gather."""
    acc = _reduce_with(cur, prod, n_steps=planner._log2_ceil(prod.shape[1]))
    return _k0_slice(acc, shape)


def _matmul_reduce(cur: ScheduleCursor, prod: PlanePack,
                   shape: Tuple[int, ...]) -> PlanePack:
    """The log2(K_pad) stride-N tree reduction of a matmul's [M', K_pad, N]
    product, returned as the [M', N] result pack of `shape`: a halving
    fold on a cursor that `folds` (`_fold_reduce`), else row-buffer shifts
    (`_shift_reduce`). Same result, same ledger."""
    if cur.folds:
        return _k0_slice(_fold_reduce(cur, prod), shape)
    return _shift_reduce(cur, prod, shape)


def _reduce_sum_body(cur: ScheduleCursor, a: PlanePack) -> PlanePack:
    acc = _reduce_with(cur, a)
    return PlanePack(planes=acc.planes, n_bits=acc.n_bits,
                     signed=acc.signed, shape=())


def reduce_sum(a: PlanePack, backend: Optional[str] = None,
               spec: Optional[ArraySpec] = None, mesh=None) -> PlanePack:
    """Sum of ALL logical elements, ceil(log2(n_words)) accesses; returns a
    scalar-shaped pack (element 0 of the tree)."""
    sched = _place(planner.plan_reduce_sum(a.n_words, stride=1,
                                           n_bits=a.n_bits), spec, a.n_words)
    return run_schedule_program(sched, _reduce_sum_body, (a,),
                                body_key=("reduce_sum",), backend=backend,
                                spec=spec, mesh=mesh)


# ---------------------------------------------------------------------------
# quantized dot / matmul
# ---------------------------------------------------------------------------


#: the resident pack builders run as one program each: op by op, the
#: broadcast layout and the codec's per-plane bits would each be held in
#: device memory (GiBs for one published-width weight)
_one_program = functools.partial(
    jax.jit, static_argnames=("m", "n_bits", "signed"))


@_one_program
def matmul_rhs_pack(b: jax.Array, m: int, n_bits: int,
                    signed: bool = True) -> PlanePack:
    """The expanded [M, K_pad, N] rhs entry pack of a matmul — the plane
    stack a ResidentSet pins so warm calls skip building (and loading) it.
    Built OUTSIDE any region trace: the result is a concrete pack whose
    planes can live in array rows across calls."""
    b = jnp.asarray(b)
    if b.ndim != 2:
        raise CimOpError(f"matmul rhs must be [K, N], got {b.shape}")
    k, n = b.shape
    k_pad = 1 << planner._log2_ceil(k)
    with jax.named_scope("cim.layout"):
        b_exp = jnp.zeros((m, k_pad, n), jnp.int32).at[:, :k, :].set(
            jnp.broadcast_to(b[None, :, :], (m, k, n)).astype(jnp.int32))
        return PlanePack.pack(b_exp, n_bits, signed=signed)


@_one_program
def batched_matmul_rhs_pack(b: jax.Array, m: int, n_bits: int,
                            signed: bool = True) -> PlanePack:
    """The expanded [B_flat * M, K_pad, N] rhs entry pack of a batched
    matmul ([*B, K, N] rhs broadcast over the lhs's M rows within each
    batch element) — the plane stack a ResidentSet pins for an attention
    K^T / V side so warm decode streams only the query past resident rows.
    Built OUTSIDE any trace, like `matmul_rhs_pack`."""
    b = jnp.asarray(b)
    if b.ndim < 3:
        raise CimOpError(f"batched matmul rhs must be [*B, K, N], "
                         f"got {b.shape}")
    k, n = int(b.shape[-2]), int(b.shape[-1])
    bf = 1
    for d in b.shape[:-2]:
        bf *= int(d)
    k_pad = 1 << planner._log2_ceil(k)
    b3 = b.reshape(bf, k, n)
    with jax.named_scope("cim.layout"):
        b_exp = jnp.zeros((bf * m, k_pad, n), jnp.int32).at[:, :k, :].set(
            jnp.broadcast_to(b3[:, None, :, :], (bf, m, k, n))
            .astype(jnp.int32).reshape(bf * m, k, n))
        return PlanePack.pack(b_exp, n_bits, signed=signed)


def _batched_matmul_with(cur: ScheduleCursor, a: jax.Array, b,
                         n_bits: int, signed: bool = True,
                         b_pack: Optional[PlanePack] = None) -> PlanePack:
    """The batched matmul dataflow over an open cursor: the batch dims
    flatten onto the word axis, the expanded operands are
    [B_flat * M, K_pad, N], and the step sequence — one shift-and-add
    multiply plus a log2(K_pad) stride-N tree reduction — is the 2-D
    `_matmul_with` dataflow verbatim with M' = B_flat * M. Correctness of
    the shared reduction follows from the 2-D argument: each (b, m) block
    is a contiguous K_pad * N word segment; the fold halves each block in
    place, and the shifts' cross-block garbage lands on discarded k > 0
    slots.

    With `b_pack` (a pinned `batched_matmul_rhs_pack`) the rhs side is
    RESIDENT: its per-batch expansion and entry pack are skipped and the
    ledger charges one zero-load reuse — decode's KV sides stay in rows
    while only the streamed lhs (the query) pays loads."""
    a = jnp.asarray(a)
    if a.ndim < 3:
        raise CimOpError(f"batched matmul needs [*B, M, K] lhs, "
                         f"got {a.shape}")
    m, k = int(a.shape[-2]), int(a.shape[-1])
    bdims = tuple(int(d) for d in a.shape[:-2])
    bf = 1
    for d in bdims:
        bf *= d
    a2 = a.reshape(bf * m, k)
    if b_pack is not None:
        mm, k_pad, n = b_pack.shape
        if mm != bf * m or k > k_pad:
            raise CimOpError(
                f"resident rhs pack {b_pack.shape} does not match lhs "
                f"{a.shape} (expanded for {bf}x{m} rows, K_pad={k_pad})")
        pb = b_pack
    else:
        b = jnp.asarray(b)
        if b.ndim != a.ndim or tuple(int(d) for d in b.shape[:-2]) != bdims \
                or int(b.shape[-2]) != k:
            raise CimOpError(
                f"batched matmul needs [*B,M,K] x [*B,K,N], "
                f"got {a.shape} {b.shape}")
        n = int(b.shape[-1])
        k_pad = 1 << planner._log2_ceil(k)
        b3 = b.reshape(bf, k, n)
        with jax.named_scope("cim.layout"):
            b_exp = jnp.zeros((bf * m, k_pad, n), jnp.int32).at[
                :, :k, :].set(
                jnp.broadcast_to(b3[:, None, :, :], (bf, m, k, n))
                .astype(jnp.int32).reshape(bf * m, k, n))
            pb = PlanePack.pack(b_exp, n_bits, signed=signed)
        cur.charge_load(n_bits, pb.n_words)
    with jax.named_scope("cim.layout"):
        a_exp = jnp.zeros((bf * m, k_pad, n), jnp.int32).at[:, :k, :].set(
            jnp.broadcast_to(a2[:, :, None], (bf * m, k, n))
            .astype(jnp.int32))
        pa = PlanePack.pack(a_exp, n_bits, signed=signed)
    cur.charge_load(n_bits, pa.n_words)
    if b_pack is not None:
        cur.charge_resident(n_bits, pb.n_words)

    prod = _multiply_with(cur, pa, pb)
    return _matmul_reduce(cur, prod, bdims + (m, n))


def _matmul_with(cur: ScheduleCursor, a: jax.Array, b,
                 n_bits: int, signed: bool = True,
                 b_pack: Optional[PlanePack] = None) -> PlanePack:
    """The matmul dataflow over an open cursor: broadcast [M, K_pad, N]
    operand layout, ONE shift-and-add multiply, log2(K_pad) stride-N tree
    reduction to an [M, N] pack (`_matmul_reduce`). Shared by the
    standalone `matmul` wrapper and the lowering compiler's fused-region
    executor (which passes a region cursor mid-schedule).

    On a traced unbanked cursor — every region program the model's call
    sites lower — the reduction folds in halves, computing only the words
    its result keeps, while the ledger charges each planned add at the
    modelled full-row M * K_pad * N words. Banked and eager cursors shift
    the whole row buffer and gather the k = 0 slice.

    With `b_pack` (a pinned `matmul_rhs_pack`) the rhs side is RESIDENT:
    its expansion and entry pack are skipped entirely — the streamed lhs
    pays its load, the rhs charges one zero-load resident reuse — which is
    the paper's stored-operand execution made literal."""
    a = jnp.asarray(a)
    if b_pack is not None:
        if a.ndim != 2:
            raise CimOpError(f"matmul needs [M,K] lhs, got {a.shape}")
        m, k = a.shape
        mm, k_pad, n = b_pack.shape
        if mm != m or k > k_pad:
            raise CimOpError(
                f"resident rhs pack {b_pack.shape} does not match lhs "
                f"{a.shape} (expanded for M={mm}, K_pad={k_pad})")
        pb = b_pack
    else:
        b = jnp.asarray(b)
        if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
            raise CimOpError(
                f"matmul needs [M,K] x [K,N], got {a.shape} {b.shape}")
        m, k = a.shape
        n = b.shape[1]
        k_pad = 1 << planner._log2_ceil(k)
        with jax.named_scope("cim.layout"):
            b_exp = jnp.zeros((m, k_pad, n), jnp.int32).at[:, :k, :].set(
                jnp.broadcast_to(b[None, :, :], (m, k, n)).astype(jnp.int32))
            pb = PlanePack.pack(b_exp, n_bits, signed=signed)
        cur.charge_load(n_bits, pb.n_words)
    with jax.named_scope("cim.layout"):
        a_exp = jnp.zeros((m, k_pad, n), jnp.int32).at[:, :k, :].set(
            jnp.broadcast_to(a[:, :, None], (m, k, n)).astype(jnp.int32))
        pa = PlanePack.pack(a_exp, n_bits, signed=signed)
    cur.charge_load(n_bits, pa.n_words)
    if b_pack is not None:
        cur.charge_resident(n_bits, pb.n_words)

    prod = _multiply_with(cur, pa, pb)
    return _matmul_reduce(cur, prod, (m, n))


def matmul(a: jax.Array, b: Optional[jax.Array] = None, n_bits: int = 8,
           backend: Optional[str] = None,
           spec: Optional[ArraySpec] = None, mesh=None,
           b_pack: Optional[PlanePack] = None) -> jax.Array:
    """Exact intN x intN -> int32 matmul through the CiM array.

    a : int [M, K], b : int [K, N], entries representable in n_bits signed.
    Lowered to ONE shift-and-add multiply over the broadcast [M, K_pad, N]
    operand layout plus a log2(K_pad) stride-N tree reduction — the whole
    contraction is (2*n_bits - 1) + ceil(log2 K) accesses regardless of M
    and N. Word-level parallelism is the CiM scaling argument; the operand
    broadcast is the (honest) cost of it.

    With `b_pack` (a pinned `matmul_rhs_pack`; `b` may then be None) the
    rhs is RESIDENT: the schedule names it so, the compiled program keys on
    that residency, and only the lhs pays operand-load charges.
    """
    a = jnp.asarray(a)
    if b_pack is not None:
        m2, k_pad, n = b_pack.shape
        sched = _place(planner.plan_matmul(k_pad, n, n_bits=n_bits,
                                           signed=True, resident_rhs=True),
                       spec, m2 * k_pad * n)

        def body_res(cur, a_, bp):
            return _matmul_with(cur, a_, None, n_bits, b_pack=bp).unpack()

        return run_schedule_program(sched, body_res, (a, b_pack),
                                    body_key=("matmul", n_bits, "resident"),
                                    backend=backend, spec=spec, mesh=mesh)
    b = jnp.asarray(b)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise CimOpError(f"matmul needs [M,K] x [K,N], got {a.shape} {b.shape}")
    m, k = a.shape
    n = b.shape[1]
    k_pad = 1 << planner._log2_ceil(k)
    sched = _place(planner.plan_matmul(k, n, n_bits=n_bits, signed=True),
                   spec, m * k_pad * n)

    def body(cur, a_, b_):
        # the broadcast-layout build, the entry packs and the exit unpack
        # all live INSIDE the step program — the whole contraction is one
        # XLA dispatch end to end
        return _matmul_with(cur, a_, b_, n_bits).unpack()

    return run_schedule_program(sched, body, (a, b),
                                body_key=("matmul", n_bits),
                                backend=backend, spec=spec, mesh=mesh)


def batched_matmul(a: jax.Array, b: Optional[jax.Array] = None,
                   n_bits: int = 8, backend: Optional[str] = None,
                   spec: Optional[ArraySpec] = None, mesh=None,
                   b_pack: Optional[PlanePack] = None) -> jax.Array:
    """Exact batched intN x intN -> int32 contraction through the CiM array.

    a : int [*B, M, K], b : int [*B, K, N] — every batch element contracts
    in the SAME (2*n_bits - 1) + ceil(log2 K) accesses as a single 2-D
    matmul: the batch dims flatten onto the word/tile axis, so batching
    scales words (and tile placement) but never the per-tile access count.

    With `b_pack` (a pinned `batched_matmul_rhs_pack`; `b` may then be
    None) the rhs is RESIDENT and only the lhs pays operand loads — the
    decode-attention execution where K^T and V live in rows and the query
    streams past them.
    """
    a = jnp.asarray(a)
    if a.ndim < 3:
        raise CimOpError(f"batched matmul needs [*B, M, K] lhs, "
                         f"got {a.shape}")
    m, k = int(a.shape[-2]), int(a.shape[-1])
    bf = 1
    for d in a.shape[:-2]:
        bf *= int(d)
    if b_pack is not None:
        mm, k_pad, n = b_pack.shape
        sched = _place(planner.plan_batched_matmul(
            bf, k_pad, n, n_bits=n_bits, signed=True, resident_rhs=True),
            spec, mm * k_pad * n)

        def body_res(cur, a_, bp):
            return _batched_matmul_with(cur, a_, None, n_bits,
                                        b_pack=bp).unpack()

        return run_schedule_program(
            sched, body_res, (a, b_pack),
            body_key=("batched_matmul", n_bits, "resident"),
            backend=backend, spec=spec, mesh=mesh)
    b = jnp.asarray(b)
    if b.ndim != a.ndim or b.shape[:-2] != a.shape[:-2] \
            or int(b.shape[-2]) != k:
        raise CimOpError(
            f"batched matmul needs [*B,M,K] x [*B,K,N], got {a.shape} "
            f"{b.shape}")
    n = int(b.shape[-1])
    k_pad = 1 << planner._log2_ceil(k)
    sched = _place(planner.plan_batched_matmul(bf, k, n, n_bits=n_bits,
                                               signed=True),
                   spec, bf * m * k_pad * n)

    def body(cur, a_, b_):
        return _batched_matmul_with(cur, a_, b_, n_bits).unpack()

    return run_schedule_program(sched, body, (a, b),
                                body_key=("batched_matmul", n_bits),
                                backend=backend, spec=spec, mesh=mesh)


# ---------------------------------------------------------------------------
# chain executor: one cursor for a fused multi-eqn region
# ---------------------------------------------------------------------------


class ChainExecutor:
    """Executes a fused region Schedule (planner.concat_schedules) through
    ONE shared cursor: each constituent op issues its planned accesses in
    order against the same cursor, so a whole multi-eqn region inherits the
    per-macro accounting guarantee — ledger accesses == region plan length,
    with every intermediate staying in the PlanePack packed domain.

    This is the execution half of the lowering compiler's region fusion
    (repro.cim.lower): lower() concatenates per-eqn schedules at trace
    time; the hybrid callable compiles each region into one step program
    (run_schedule_program) whose body drives a ChainExecutor over the
    program's traced cursor (`from_cursor`).
    """

    def __init__(self, schedule: planner.Schedule,
                 backend: Optional[str] = None,
                 spec: Optional[ArraySpec] = None, mesh=None,
                 charges: Optional[list] = None):
        self.cursor = ScheduleCursor(schedule, backend, spec=spec, mesh=mesh,
                                     charges=charges)

    @classmethod
    def from_cursor(cls, cursor: ScheduleCursor) -> "ChainExecutor":
        """Wrap an already-open cursor (the step program's traced one)."""
        self = cls.__new__(cls)
        self.cursor = cursor
        return self

    # -- single-access ops (one planned step each) --------------------------
    def execute(self, a: PlanePack, b: PlanePack,
                ops: Sequence[str]) -> engine.Outputs:
        return self.cursor.execute(a, b, ops)

    def minimum(self, a: PlanePack, b: PlanePack) -> PlanePack:
        return _minimum_with(self.cursor, a, b)

    def maximum(self, a: PlanePack, b: PlanePack) -> PlanePack:
        return _maximum_with(self.cursor, a, b)

    def abs_(self, a: PlanePack) -> PlanePack:
        return _abs_with(self.cursor, a)

    def neg(self, a: PlanePack) -> PlanePack:
        zero = PlanePack.zeros_like(a)
        return self.cursor.execute(zero, a, ("sub",))["sub"]

    # -- multi-access macros (their planned segment of the region) ----------
    def multiply(self, a: PlanePack, b: PlanePack) -> PlanePack:
        return _multiply_with(self.cursor, a, b)

    def popcount(self, a: PlanePack) -> PlanePack:
        return _popcount_with(self.cursor, a)

    def reduce_sum(self, a: PlanePack) -> PlanePack:
        acc = _reduce_with(self.cursor, a,
                           n_steps=planner._log2_ceil(max(1, a.n_words)))
        return PlanePack(planes=acc.planes, n_bits=acc.n_bits,
                         signed=acc.signed, shape=())

    def matmul(self, a: jax.Array, b, n_bits: int,
               signed: bool = True,
               b_pack: Optional[PlanePack] = None) -> PlanePack:
        return _matmul_with(self.cursor, a, b, n_bits, signed=signed,
                            b_pack=b_pack)

    def batched_matmul(self, a: jax.Array, b, n_bits: int,
                       signed: bool = True,
                       b_pack: Optional[PlanePack] = None) -> PlanePack:
        return _batched_matmul_with(self.cursor, a, b, n_bits, signed=signed,
                                    b_pack=b_pack)

    def finish(self) -> None:
        self.cursor.finish()


def dot(a: jax.Array, b: jax.Array, n_bits: int = 8,
        backend: Optional[str] = None,
        spec: Optional[ArraySpec] = None, mesh=None) -> jax.Array:
    """Exact intN x intN -> int32 dot product of two [K] vectors."""
    a = jnp.asarray(a).reshape(1, -1)
    b = jnp.asarray(b).reshape(-1, 1)
    return matmul(a, b, n_bits=n_bits, backend=backend,
                  spec=spec, mesh=mesh)[0, 0]


# ---------------------------------------------------------------------------
# integer-level convenience wrappers (pack at entry, unpack at exit)
# ---------------------------------------------------------------------------


def multiply_ints(x: jax.Array, y: jax.Array, n_bits: int = 16,
                  signed: bool = True,
                  backend: Optional[str] = None,
                  spec: Optional[ArraySpec] = None) -> jax.Array:
    p = multiply(PlanePack.pack(x, n_bits, signed=signed),
                 PlanePack.pack(y, n_bits, signed=signed), backend=backend,
                 spec=spec)
    return p.unpack()


def relu_ints(x: jax.Array, n_bits: int = 16,
              backend: Optional[str] = None,
              spec: Optional[ArraySpec] = None) -> jax.Array:
    return relu(PlanePack.pack(x, n_bits), backend=backend,
                spec=spec).unpack()


def abs_ints(x: jax.Array, n_bits: int = 16,
             backend: Optional[str] = None,
             spec: Optional[ArraySpec] = None) -> jax.Array:
    return abs_(PlanePack.pack(x, n_bits), backend=backend,
                spec=spec).unpack()


def minimum_ints(x: jax.Array, y: jax.Array, n_bits: int = 16,
                 backend: Optional[str] = None,
                 spec: Optional[ArraySpec] = None) -> jax.Array:
    return minimum(PlanePack.pack(x, n_bits), PlanePack.pack(y, n_bits),
                   backend=backend, spec=spec).unpack()


def maximum_ints(x: jax.Array, y: jax.Array, n_bits: int = 16,
                 backend: Optional[str] = None,
                 spec: Optional[ArraySpec] = None) -> jax.Array:
    return maximum(PlanePack.pack(x, n_bits), PlanePack.pack(y, n_bits),
                   backend=backend, spec=spec).unpack()


def popcount_ints(x: jax.Array, n_bits: int = 16,
                  backend: Optional[str] = None,
                  spec: Optional[ArraySpec] = None) -> jax.Array:
    return popcount(PlanePack.pack(x, n_bits), backend=backend,
                    spec=spec).unpack()


def reduce_sum_ints(x: jax.Array, n_bits: int = 16, signed: bool = True,
                    backend: Optional[str] = None,
                    spec: Optional[ArraySpec] = None) -> jax.Array:
    return reduce_sum(PlanePack.pack(x, n_bits, signed=signed),
                      backend=backend, spec=spec).unpack()
