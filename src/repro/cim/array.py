"""Banked CiM array substrate: physical geometry, tile placement, residency.

The engine (repro.cim.engine) treats the memory as one infinitely wide
array; real ADRA arrays are banks of subarrays of rows x bitlines. This
module is the geometry layer between the two: an `ArraySpec` describes the
physical array, and its `plan()` method turns any operand word count into a
`TilePlan` — which words go to which bank activation — that the tiling
dispatcher (repro.cim.dispatch) executes and the accounting ledger charges.

Layout convention (the engine's transposed bit-serial form): inside a
subarray each bitline column holds ONE word and row p holds bit-plane p, so
one dual-row activation computes over `bitline_words` words in parallel and
the operand/result plane stacks occupy rows. A bank activation drives all
of its subarrays at once (shared wordline drivers), so one bank serves
`subarrays * bitline_words` words per access; banks operate concurrently,
and tiles beyond `banks` per round serialize into waves — the contention
the per-bank ledger model charges.

The RESIDENT region: FeFET rows are nonvolatile, so an operand written once
(a weight plane stack, a paged KV block) can stay in its rows across calls —
the paper's stored-operand assumption. A `ResidentSet` tracks those pinned
plane stacks per bank under the row budget: every pin charges the ledger ONE
operand load (per tile), every reuse charges zero, and rows claimed by
residents shrink what `check_fits` allows a streaming access (the combined
check names the resident occupancy in its error). Pins are LRU-evicted under
pressure; `reserve()` entries (KV pages) are not evictable and fail loudly
instead. Counters aggregate process-wide into `dispatch.cache_stats()`.

Defaults are calibrated to the paper's 1024-row FeFET array
(1024 x 1024 subarray => 1024 words per subarray activation).
"""
from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict
from typing import Any, Dict, Optional, Sequence, Tuple

from . import opset


@dataclasses.dataclass(frozen=True)
class ArraySpec:
    """Physical geometry of a banked ADRA CiM array.

    banks          : independently activatable banks (concurrent).
    subarrays      : subarrays per bank, activated together per access.
    rows           : wordlines per subarray — bounds the plane budget of one
                     access (two operand stacks + every requested output).
    bitline_words  : words served per subarray activation (one word per
                     bitline column in the transposed bit-serial layout);
                     must be a multiple of 32 so tiles align with the packed
                     uint32 lanes of PlanePack.
    disabled_banks : banks taken out of service (whole-bank failures).
                     Placement round-robins over the ENABLED banks only;
                     the default () keeps degraded and healthy specs
                     distinct hashable values, so every spec-keyed cache
                     (compiled programs, resident-set registry, lowered
                     callables) naturally separates the two.
    """

    banks: int = 4
    subarrays: int = 4
    rows: int = 1024
    bitline_words: int = 1024
    disabled_banks: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.banks < 1 or self.subarrays < 1 or self.rows < 1:
            raise opset.CimOpError(f"degenerate ArraySpec: {self}")
        if self.bitline_words < 32 or self.bitline_words % 32:
            raise opset.CimOpError(
                f"bitline_words must be a positive multiple of 32 (packed "
                f"uint32 lanes), got {self.bitline_words}")
        dead = tuple(sorted(set(int(b) for b in self.disabled_banks)))
        if any(b < 0 or b >= self.banks for b in dead):
            raise opset.CimOpError(
                f"disabled_banks {dead} outside [0, {self.banks})")
        if len(dead) >= self.banks:
            raise opset.CimOpError(
                f"every bank of {self} disabled: nothing left to remap to")
        object.__setattr__(self, "disabled_banks", dead)

    @property
    def enabled_banks(self) -> Tuple[int, ...]:
        """Live bank ids, in order — what placement round-robins over."""
        if not self.disabled_banks:
            return tuple(range(self.banks))
        dead = set(self.disabled_banks)
        return tuple(b for b in range(self.banks) if b not in dead)

    @property
    def n_enabled(self) -> int:
        return self.banks - len(self.disabled_banks)

    def disable_bank(self, bank: int) -> "ArraySpec":
        """The degraded spec with `bank` also dead (raises via __post_init__
        when that would leave no live banks)."""
        return dataclasses.replace(
            self, disabled_banks=self.disabled_banks + (int(bank),))

    @property
    def tile_words(self) -> int:
        """Words one bank activation serves = the tiling granule."""
        return self.subarrays * self.bitline_words

    @property
    def parallel_words(self) -> int:
        """Words the whole array serves per wave (all LIVE banks active)."""
        return self.n_enabled * self.tile_words

    def check_fits(self, n_bits: int, ops: Sequence[str],
                   resident_rows: int = 0) -> None:
        """One access must fit its operand + result planes in the rows of a
        subarray: 2 operand stacks of n_bits plus every requested output —
        MINUS whatever rows the resident region has pinned (the combined
        streaming + residency budget of one bank)."""
        need = 2 * n_bits + sum(opset.out_rows(op, n_bits) for op in ops)
        if need + resident_rows > self.rows:
            occupancy = (f" with {resident_rows} rows held by resident "
                         f"operands" if resident_rows else "")
            raise opset.CimOpError(
                f"access needs {need} rows (2x{n_bits} operand planes + "
                f"outputs {tuple(ops)}){occupancy} but subarrays have "
                f"{self.rows}")

    def plan(self, n_words: int) -> "TilePlan":
        if n_words < 1:
            raise opset.CimOpError(f"cannot place {n_words} words")
        n_tiles = -(-n_words // self.tile_words)
        return TilePlan(n_words=n_words, tile_words=self.tile_words,
                        n_tiles=n_tiles, banks=self.banks,
                        enabled=(self.enabled_banks
                                 if self.disabled_banks else ()))


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Placement of an operand pair onto a banked array: tile t covers words
    [t * tile_words, (t+1) * tile_words) and runs on the t-th live bank in
    round-robin order during wave `t // n_live` — the layout that balances
    banks best for contiguous operands. `enabled` names the live banks of a
    DEGRADED array (dead banks are skipped, waves stretch accordingly); the
    default () means all `banks` are live, so healthy plans hash and compare
    exactly as before. Static and hashable: it is part of the
    compiled-schedule cache key."""

    n_words: int
    tile_words: int
    n_tiles: int
    banks: int
    enabled: Tuple[int, ...] = ()

    @property
    def live_banks(self) -> Tuple[int, ...]:
        return self.enabled if self.enabled else tuple(range(self.banks))

    @property
    def n_live(self) -> int:
        return len(self.enabled) if self.enabled else self.banks

    @property
    def lanes_per_tile(self) -> int:
        return self.tile_words // 32

    @property
    def waves(self) -> int:
        """Sequential activations on the busiest bank (the critical path)."""
        return -(-self.n_tiles // self.n_live)

    @property
    def pad_words(self) -> int:
        """Idle bitline columns of the last tile (activated but operand-less)."""
        return self.n_tiles * self.tile_words - self.n_words

    def bank_of(self, tile: int) -> int:
        """Physical bank of tile `tile` — never a disabled bank."""
        live = self.live_banks
        return live[tile % len(live)]

    def bank_counts(self, n_devices: int = 1) -> Dict[Tuple[int, int], int]:
        """Activations per (device, bank) — what the ledger charges.

        Closed-form: device d owns the contiguous tile block [d*per_dev,
        min((d+1)*per_dev, n_tiles)) and live bank slot s takes every tile
        ≡ s mod n_live inside it, so each slot is a count of a residue
        class in a range — O(devices * banks), never O(n_tiles)
        (model-scale operands place hundreds of thousands of tiles per
        schedule step). Keys are PHYSICAL bank ids; disabled banks never
        appear."""
        live = self.live_banks
        n_live = len(live)

        def upto(x: int, s: int) -> int:
            # tiles t in [0, x) with t % n_live == s  (0 <= s < n_live)
            return (x - s + n_live - 1) // n_live

        per_dev = -(-self.n_tiles // n_devices)
        counts: Dict[Tuple[int, int], int] = {}
        for d in range(n_devices):
            lo = min(d * per_dev, self.n_tiles)
            hi = min(lo + per_dev, self.n_tiles)
            for s, b in enumerate(live):
                n = upto(hi, s) - upto(lo, s)
                if n:
                    counts[(d, b)] = n
        return counts


#: the paper's array, four banks of four subarrays
DEFAULT_SPEC = ArraySpec()


# ---------------------------------------------------------------------------
# the resident region: operands pinned in bank rows across calls
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ResidentEntry:
    """One pinned occupant of the resident region.

    pack         : the pinned PlanePack (None for a `reserve()` row claim,
                   e.g. a paged KV block whose values live outside the
                   packed domain but whose rows are spoken for).
    rows_by_bank : rows this entry holds in each bank — n_bits plane rows
                   per tile placed there (tiles on the same bank stack),
                   plus the SECDED parity rows when the set runs with ECC.
    fingerprint  : identity of the source buffers; a mismatched `get()`
                   drops the entry (stale pin) instead of returning it.
    evictable    : LRU-evictable under pin pressure; reservations are not.
    ecc_parity   : uint32[r+1, W] SECDED parity planes of the pinned pack
                   (None when the set runs unprotected).
    scrubbed_s   : fault-model clock of the last verify/scrub — what the
                   retention-decay model integrates flips over.
    """

    key: Tuple
    pack: Any
    rows_by_bank: Dict[int, int]
    words32: float = 0.0
    fingerprint: Tuple = ()
    evictable: bool = True
    aux: Any = None
    hits: int = 0
    ecc_parity: Any = None
    scrubbed_s: float = 0.0


class ResidentSet:
    """Row-budget-checked resident region of one banked array.

    `pin(key, pack)` writes a plane stack into rows once — charging the
    ledger the operand-load accesses a streaming execution would pay per
    call — and keeps it addressable across calls; `get(key)` is the warm
    path (zero load charges, `resident_reuses` counted by the caller's
    schedule). Pins are LRU-ordered and evicted when a new pin does not fit
    the per-bank row budget (`rows - reserve_rows`); `reserve()` claims
    rows without a pack (paged KV blocks) and is never evicted silently.
    """

    def __init__(self, spec: Optional[ArraySpec] = None,
                 reserve_rows: int = 0, ecc: bool = False):
        self.spec = spec or DEFAULT_SPEC
        if reserve_rows < 0 or reserve_rows >= self.spec.rows:
            raise opset.CimOpError(
                f"reserve_rows must be in [0, {self.spec.rows}), "
                f"got {reserve_rows}")
        self.reserve_rows = reserve_rows
        self.ecc = bool(ecc)
        self._entries: "OrderedDict[Tuple, ResidentEntry]" = OrderedDict()
        self.pins = 0
        self.reserves = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self.ecc_corrected = 0
        self.ecc_uncorrected = 0
        self.ecc_verifies = 0
        _ALL_SETS.add(self)

    # -- occupancy ----------------------------------------------------------
    def rows_per_bank(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for e in self._entries.values():
            for b, r in e.rows_by_bank.items():
                out[b] = out.get(b, 0) + r
        return out

    @property
    def resident_rows(self) -> int:
        """Rows held in the busiest bank — what a streaming access loses."""
        return max(self.rows_per_bank().values(), default=0)

    def _rows_for(self, n_bits: int, n_words: int) -> Dict[int, int]:
        """Per-bank rows of an n_bits pack of n_words: n_bits plane rows
        per tile on the tile's round-robin bank (same-bank tiles stack)."""
        plan = self.spec.plan(n_words)
        return {b: n_bits * n for (_d, b), n in plan.bank_counts(1).items()}

    def _load_tiles(self, n_words: int) -> int:
        """Load accesses one pin of n_words charges: one per tile."""
        return self.spec.plan(n_words).n_tiles

    def fits(self, rows_by_bank: Dict[int, int]) -> bool:
        occ = self.rows_per_bank()
        budget = self.spec.rows - self.reserve_rows
        return all(occ.get(b, 0) + r <= budget
                   for b, r in rows_by_bank.items())

    # -- lifecycle ----------------------------------------------------------
    def peek(self, key: Tuple,
             fingerprint: Optional[Tuple] = None) -> bool:
        """Presence+fingerprint test WITHOUT counters or LRU movement — the
        warm-pass probe (a real `get` follows for entries actually used)."""
        entry = self._entries.get(key)
        return entry is not None and (
            fingerprint is None or entry.fingerprint == tuple(fingerprint))

    def get(self, key: Tuple,
            fingerprint: Optional[Tuple] = None) -> Optional[ResidentEntry]:
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            _STATS["resident_misses"] += 1
            return None
        if fingerprint is not None and entry.fingerprint != fingerprint:
            # the source buffers changed identity: the pinned rows are stale
            del self._entries[key]
            self.invalidations += 1
            _STATS["resident_invalidations"] += 1
            self.misses += 1
            _STATS["resident_misses"] += 1
            return None
        if entry.ecc_parity is not None and not self._verify(entry):
            # uncorrectable: the rows are data loss; the entry was dropped
            # (invalidation) so the caller rebuilds from the source
            self.misses += 1
            _STATS["resident_misses"] += 1
            return None
        entry.hits += 1
        self.hits += 1
        _STATS["resident_hits"] += 1
        self._entries.move_to_end(key)
        return entry

    def pin(self, key: Tuple, pack, fingerprint: Tuple = (),
            aux: Any = None) -> ResidentEntry:
        """Pack `pack` into resident rows (evicting LRU pins to fit) and
        charge the one-time operand load the pin replaces per call. With
        `ecc` on, the SECDED parity planes are encoded here, stored as
        extra rows of the same banks, and their row writes charged as ECC
        overhead (`Ledger.charge_ecc`)."""
        from .accounting import LEDGER

        if key in self._entries:
            del self._entries[key]        # re-pin: release the stale rows
        parity = None
        n_ecc = 0
        if self.ecc:
            from . import faults as faults_mod
            from .planepack import ecc_encode, ecc_plane_count
            import numpy as _np
            parity = ecc_encode(_np.asarray(pack.planes))
            n_ecc = ecc_plane_count(pack.n_bits)
        rows = self._rows_for(pack.n_bits + n_ecc, pack.n_words)
        self._make_room(key, rows)
        words32 = pack.n_words * pack.n_bits / 32.0
        fm = None
        if self.ecc:
            fm = faults_mod.active()
        entry = ResidentEntry(key=key, pack=pack, rows_by_bank=rows,
                              words32=words32, fingerprint=tuple(fingerprint),
                              evictable=True, aux=aux, ecc_parity=parity,
                              scrubbed_s=(fm.clock() if fm is not None
                                          else 0.0))
        self._entries[key] = entry
        self.pins += 1
        _STATS["resident_pins"] += 1
        n_tiles = self._load_tiles(pack.n_words)
        LEDGER.charge_load(pack.n_bits, pack.n_words, n_tiles=n_tiles)
        if n_ecc:
            LEDGER.charge_ecc(n_ecc, pack.n_words, n_tiles=n_tiles)
        return entry

    # -- ECC verify / scrub --------------------------------------------------

    def _verify(self, entry: ResidentEntry, decay_s: float = 0.0) -> bool:
        """One ECC pass over a protected entry: inject whatever the active
        fault model says the rows took (per-get resident BER, plus
        `decay_s` seconds of retention decay on the scrub path), then
        SECDED-verify and repair. Returns False — after invalidating the
        entry — when the damage was uncorrectable."""
        import dataclasses as _dc

        import jax.numpy as _jnp
        import numpy as _np

        from . import faults as faults_mod
        from .accounting import LEDGER
        from .planepack import ecc_check_correct

        fm = faults_mod.active()
        planes = _np.asarray(entry.pack.planes)
        parity = entry.ecc_parity
        if fm is not None:
            planes, _ = fm.corrupt_resident(planes)
            if decay_s > 0.0:
                flips = fm.decay_bits(
                    decay_s, planes.size * 32 + parity.size * 32)
                if flips:
                    planes = _np.array(planes, copy=True)
                    flat = planes.reshape(-1)
                    idx = fm.rng.integers(0, planes.size * 32, size=flips)
                    for i in _np.asarray(idx):
                        flat[i // 32] ^= _np.uint32(1) << _np.uint32(i % 32)
                    fm.injected += flips
                    faults_mod._STATS["fault_injected"] += flips
                    LEDGER.charge_fault(injected=int(flips))
            entry.scrubbed_s = fm.clock()
        fixed, fixed_par, corrected, uncorrected = \
            ecc_check_correct(planes, parity)
        self.ecc_verifies += 1
        _STATS["ecc_verifies"] += 1
        from .planepack import ecc_plane_count
        LEDGER.charge_ecc(ecc_plane_count(entry.pack.n_bits),
                          entry.pack.n_words,
                          n_tiles=self.spec.plan(entry.pack.n_words).n_tiles)
        if corrected:
            self.ecc_corrected += corrected
            _STATS["ecc_corrected"] += corrected
        if uncorrected:
            self.ecc_uncorrected += uncorrected
            _STATS["ecc_uncorrected"] += uncorrected
        if fm is not None:
            fm.record_verify(corrected, uncorrected)
        if uncorrected:
            self._entries.pop(entry.key, None)
            self.invalidations += 1
            _STATS["resident_invalidations"] += 1
            if fm is not None and fm.config.raise_on_uncorrectable:
                raise faults_mod.UncorrectableFaultError(
                    f"resident entry {entry.key!r}: {uncorrected} "
                    f"uncorrectable bit(s); entry invalidated — re-pin "
                    f"and retry")
            return False
        if corrected or fm is not None:
            entry.pack = _dc.replace(entry.pack,
                                     planes=_jnp.asarray(fixed))
            entry.ecc_parity = fixed_par
        return True

    def scrub(self) -> Dict[str, int]:
        """Walk every protected pin, integrate retention decay since its
        last verify, and repair what SECDED can (uncorrectable entries are
        invalidated so the next `get` misses and rebuilds). The periodic
        background pass a serving process runs between steps."""
        from . import faults as faults_mod

        fm = faults_mod.active()
        now = fm.clock() if fm is not None else 0.0
        corrected0 = self.ecc_corrected
        uncorrected0 = self.ecc_uncorrected
        scanned = 0
        dropped = 0
        for entry in list(self._entries.values()):
            if entry.ecc_parity is None:
                continue
            scanned += 1
            decay_s = max(0.0, now - entry.scrubbed_s) if fm is not None \
                else 0.0
            if not self._verify(entry, decay_s=decay_s):
                dropped += 1
        _STATS["ecc_scrubs"] += 1
        return {"scanned": scanned, "dropped": dropped,
                "corrected": self.ecc_corrected - corrected0,
                "uncorrected": self.ecc_uncorrected - uncorrected0}

    def reserve(self, key: Tuple, n_rows: int, bank: int = 0,
                words32: float = 0.0,
                fingerprint: Tuple = ()) -> ResidentEntry:
        """Claim `n_rows` on one bank without a pack (a paged KV block's
        rows). Not evictable: a failed fit raises instead of silently
        dropping someone else's state."""
        if key in self._entries:
            del self._entries[key]
        rows = {int(bank) % self.spec.banks: int(n_rows)}
        self._make_room(key, rows)
        entry = ResidentEntry(key=key, pack=None, rows_by_bank=rows,
                              words32=words32, fingerprint=tuple(fingerprint),
                              evictable=False)
        self._entries[key] = entry
        self.reserves += 1
        _STATS["resident_reserves"] += 1
        return entry

    def _make_room(self, key: Tuple, rows_by_bank: Dict[int, int]) -> None:
        budget = self.spec.rows - self.reserve_rows
        if any(r > budget for r in rows_by_bank.values()):
            raise opset.CimOpError(
                f"resident entry {key!r} needs {max(rows_by_bank.values())} "
                f"rows on one bank but the resident budget is {budget} "
                f"(rows {self.spec.rows} - reserve {self.reserve_rows})")
        while not self.fits(rows_by_bank):
            victim = next((k for k, e in self._entries.items()
                           if e.evictable), None)
            if victim is None:
                occ = self.rows_per_bank()
                raise opset.CimOpError(
                    f"resident entry {key!r} does not fit: occupancy "
                    f"{occ} of {budget} rows/bank is all reservations")
            del self._entries[victim]
            self.evictions += 1
            _STATS["resident_evictions"] += 1

    def release(self, key: Tuple) -> bool:
        return self._entries.pop(key, None) is not None

    def clear(self) -> None:
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._entries), "pins": self.pins,
                "reserves": self.reserves,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "ecc_verifies": self.ecc_verifies,
                "ecc_corrected": self.ecc_corrected,
                "ecc_uncorrected": self.ecc_uncorrected,
                "resident_rows": self.resident_rows}


class UnbankedResidentSet(ResidentSet):
    """The resident region of the unbanked array that `spec=None` lowering
    computes on. That array has no bank geometry: a streamed entry pack
    there costs one load access whatever its size, and nothing bounds its
    rows. Pins are modelled the same way — one load access each and no row
    budget — so residency is compared with streaming on one geometry."""

    def _rows_for(self, n_bits: int, n_words: int) -> Dict[int, int]:
        return {0: n_bits}

    def _load_tiles(self, n_words: int) -> int:
        return 1

    def _make_room(self, key: Tuple, rows_by_bank: Dict[int, int]) -> None:
        return None


#: every live ResidentSet (weak: test-local sets vanish with their tests)
_ALL_SETS: "weakref.WeakSet[ResidentSet]" = weakref.WeakSet()

#: process-wide counters surfaced through dispatch.cache_stats()
_STATS: Dict[str, int] = {}


def _reset_stats() -> None:
    _STATS.update(resident_pins=0, resident_reserves=0, resident_hits=0,
                  resident_misses=0, resident_evictions=0,
                  resident_invalidations=0,
                  ecc_verifies=0, ecc_corrected=0, ecc_uncorrected=0,
                  ecc_scrubs=0)


_reset_stats()

#: process-wide resident set per geometry (the one `resident_rows_for`
#: consults and the serving stack shares between weight pins and KV pages);
#: the key None holds the unbanked array's set (`lowering_resident_set`)
_RESIDENT_SETS: Dict[Optional[ArraySpec], ResidentSet] = {}

#: whether registry ResidentSets are created ECC-protected (serving turns
#: this on before building its lowered state; default off keeps the
#: committed ledger/bench baselines exact)
_DEFAULT_ECC: bool = False

#: process-wide spec override: the failover lever. Layers that default to
#: spec=None resolve through `current_spec()`, so flipping this to a
#: degraded ArraySpec re-routes every subsequent lowering/pin/dispatch
#: through the degraded geometry — fresh spec-keyed caches and all.
_CURRENT_SPEC: Optional[ArraySpec] = None


def set_resident_ecc(on: bool) -> bool:
    """Make future registry ResidentSets ECC-protected (or not); returns
    the previous setting. Existing sets keep their mode — call
    `clear_resident()` first to rebuild them protected."""
    global _DEFAULT_ECC
    prev = _DEFAULT_ECC
    _DEFAULT_ECC = bool(on)
    return prev


def resident_ecc_default() -> bool:
    return _DEFAULT_ECC


def set_current_spec(spec: Optional[ArraySpec]) -> Optional[ArraySpec]:
    """Install the process-wide spec override (None restores DEFAULT_SPEC
    resolution); returns the previous override."""
    global _CURRENT_SPEC
    prev = _CURRENT_SPEC
    _CURRENT_SPEC = spec
    return prev


def current_spec() -> ArraySpec:
    """What `spec=None` means right now: the failover override if one is
    installed, else the paper's DEFAULT_SPEC."""
    return _CURRENT_SPEC if _CURRENT_SPEC is not None else DEFAULT_SPEC


def spec_override() -> Optional[ArraySpec]:
    """The raw failover override (None when the process is healthy).
    Call sites whose `spec=None` historically meant UNBANKED lowering
    (models.layers) consult this — they must not pick up DEFAULT_SPEC."""
    return _CURRENT_SPEC


def resident_set(spec: Optional[ArraySpec] = None) -> ResidentSet:
    """The process-wide ResidentSet for `spec` (`current_spec()` when None).

    Registry sets keep a quarter of the rows as reserve: headroom the
    combined `check_fits` budget guarantees streamed access planes — pins
    can never squeeze an access out of its own subarray."""
    spec = spec or current_spec()
    rs = _RESIDENT_SETS.get(spec)
    if rs is None:
        rs = _RESIDENT_SETS[spec] = ResidentSet(
            spec, reserve_rows=spec.rows // 4, ecc=_DEFAULT_ECC)
    return rs


def lowering_resident_set(spec: Optional[ArraySpec]) -> ResidentSet:
    """The registry set a lowering on `spec` pins into: `resident_set(spec)`
    when banked, the unbanked array's set (`UnbankedResidentSet`) when
    `spec` is None."""
    if spec is not None:
        return resident_set(spec)
    rs = _RESIDENT_SETS.get(None)
    if rs is None:
        rs = _RESIDENT_SETS[None] = UnbankedResidentSet(ecc=_DEFAULT_ECC)
    return rs


def resident_rows_for(spec: Optional[ArraySpec]) -> int:
    """Busiest-bank resident occupancy of the registry set for `spec` —
    what the dispatcher folds into the combined check_fits budget."""
    rs = _RESIDENT_SETS.get(spec or current_spec())
    return rs.resident_rows if rs is not None else 0


def resident_stats() -> Dict[str, int]:
    """Aggregated pin/hit/eviction counters across every ResidentSet."""
    out = dict(_STATS)
    out["resident_entries"] = sum(len(s) for s in _ALL_SETS)
    out["resident_rows"] = max((s.resident_rows for s in _ALL_SETS),
                               default=0)
    return out


def clear_resident() -> None:
    """Drop every registry ResidentSet and zero the aggregate counters."""
    for rs in list(_ALL_SETS):
        rs.clear()
    _RESIDENT_SETS.clear()
    _reset_stats()
