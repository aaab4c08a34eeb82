"""Distributed BSP miner: LCM+LAMP with lifeline work stealing (paper §4).

One logical miner per device.  The whole search runs as a single compiled
`shard_map` program over a 1-D mesh axis "miners"; each superstep
(`lax.while_loop` body) is a pipeline of three phase modules:

  1. EXPAND   core/expand.py — pop up to `expand_batch` nodes; one
              popcount-GEMM gives every extension's support; deferred-PPC
              validation, closed-set counting, child generation (core/lcm.py
              documents the deferred-PPC scheme).
  2. STEAL    core/steal.py — one lifeline/random exchange round over the
              schedule from core/lifeline.py; REQUEST rides the hunger
              census, GIVE/REJECT is one packed ppermute, gated via
              `lax.cond` on "anyone hungry" (DESIGN.md §2/§6).
  3. GLOBAL   core/global_sync.py — the [P]-int hunger census doubles as
              the exact BSP termination test (paper §4.3's DTD is only
              needed on the async host plane); mode "lamp1" additionally
              psums the *since-last-sync delta* of the support histogram
              every `sync_period` supersteps and recomputes lambda (paper
              §4.4's piggyback; staleness only costs work, never
              correctness).

Each phase runs under a `jax.named_scope` — `expand`, `steal` (the
hunger census and the exchange), `sync` (the lambda sync, the
superstep's counters and termination, the loop condition and the
terminal psums), plus `trace` for the ring write when `trace_period >
0` — so the compiled program's op metadata, and with it a device
profile, names the part of the superstep each op belongs to (DESIGN.md
§9).  Scopes change metadata only, never the instructions.

Each per-miner stack is a circular deque over fixed [stack_cap, W] storage
(core/deque.py): EXPAND pops/pushes at the logical top by pointer
arithmetic, a steal donates the logical bottom-k with O(steal_max) gathers
and advances the bottom pointer — nothing ever shifts.

This module holds only the config, the while-loop driver that wires the
phases together, and the host-side pre/postprocess; every version-sensitive
JAX API (shard_map, collectives, mesh) lives in core/collectives.py.

Node payload (fixed size, steal-friendly):  occ [W]u32, core i32, pc i32,
sup i32, flags i32   (flags bit0: "resume" node — already counted, continues
child generation past the per-superstep push cap).

Modes:
  lamp1   dynamic lambda by support increase  -> lambda_final
  count   static min_sup                      -> k = CS(min_sup)
  test    static min_sup + delta              -> #significant + pattern records
  count2d static min_sup (+delta=alpha)       -> 2-D (sup x pos-sup) histogram
                                                 + alpha-level pattern records

The hypothesis test is pluggable (`statistic`, a repro.stats registry name):
modes "test"/"count2d" trace the statistic's device P-value into their
emission gate (distinct compiled programs per statistic; statistic=None
emits every counted closed set — the closed-frequent objective), while
"lamp1"/"count" consume it only as the host-built Tarone threshold table
(runtime data — their programs are statistic-free).

Pattern records (modes "test"/"count2d", DESIGN.md §4): each significant node
appends (occ [W]u32, core, sup, pos_sup) to a fixed out_cap buffer — the same
dense payload shape as stack nodes — and repro.results reconstructs the
closure itemsets host-side; overflowed emissions are counted (emit_dropped)
and surfaced as a RuntimeWarning from mine().

The program dims are *shape buckets* (DESIGN.md §5): arrays are sized by
padded (transactions, positives, items) while the dataset's actual counts
arrive as runtime scalars, so one compiled program serves every same-bucket
dataset.  This module provides the building blocks — `pack_problem` /
`deal_roots` (host pre), `build_phase_program` / `make_phase_args`
(compile + call), `postprocess_phase` (host post) — plus the one-shot
`mine()`.  The LAMP stagings (three_phase | fused23) live in
`repro.api.session.PIPELINES` as functions over a compile-once
`MinerSession`; the legacy `lamp_distributed` dict entry survives here as
a deprecation shim.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.obs.trace import N_FIELDS, SuperstepTrace, decode_trace
from repro.stats import get_statistic
from repro.topo.topology import Topology

from . import collectives
from .bitmap import (
    DEFAULT_ITEM_TILE,
    BitmapLayout,
    full_occ,
    item_tiling,
    num_words,
    pack_db,
    supports_np,
)
from .collectives import MINERS_AXIS
from .expand import build_expand
from .global_sync import build_global_sync, hunger_census, recompute_lambda
from .lifeline import LifelineSchedule, build_schedule
from .stats import STAT_NAMES, Stat
from .steal import build_steal_round

INT_MAX = np.int32(2**31 - 1)

_NSTAT = len(STAT_NAMES)

#: the engine's pass modes (see module docstring); anything else is a typo
VALID_MODES = ("lamp1", "count", "test", "count2d")


@dataclass(frozen=True)
class EngineConfig:
    expand_batch: int = 16         # B: nodes popped per device per superstep
    stack_cap: int = 8192          # CAP
    steal_max: int = 256           # T: max nodes per GIVE
    push_cap: int = 1024           # C: max child pushes per superstep
    out_cap: int = 1024            # significant-sample buffer (mode="test")
    max_steps: int = 100_000
    n_random_perms: int = 4
    seed: int = 0
    steal_enabled: bool = True     # False = the paper's "naive approach" (§5.4)
    kernel_impl: str = "auto"      # "auto" | kernels/support_count/ops.VALID_IMPLS
    #: resolved (block_b, block_m, block_w) for the Pallas kernel; None lets
    #: the autotuner choose at trace time.  RuntimeConfig.resolve pins the
    #: tuned triple here so it joins the compiled-program cache key.
    kernel_blocks: tuple[int, int, int] | None = None
    #: superstep trace sampling period: 0 = off; k > 0 records one
    #: [N_FIELDS]i32 record (repro.obs.trace.TraceField) every k-th
    #: superstep into a [trace_cap, N_FIELDS] device ring (DESIGN.md §9)
    trace_period: int = 0
    trace_cap: int = 0             # ring slots; required > 0 when tracing
    sync_period: int = 4           # supersteps between lambda/histogram syncs
    #: checkpoint cadence (DESIGN.md §11): 0 = the classic whole-phase
    #: program; k > 0 compiles the *segmented* program — the BSP carry
    #: round-trips to the host every k supersteps so it can be checkpointed
    #: (ckpt/mining.py), restored elastically, and stopped cooperatively at
    #: a superstep boundary.  Part of the program cache key by construction
    #: (the key holds the resolved EngineConfig), so segmented and classic
    #: programs never collide.
    ckpt_period: int = 0
    #: machine shape (repro.topo): None = the classic flat 1-D "miners"
    #: mesh; a Topology switches the pass onto the 2-D [hosts, local] mesh
    #: with the hierarchical two-level lifeline schedule (intra-host rounds
    #: cheap and frequent, cross-host rounds rare).  Frozen and hashable,
    #: so flat and hierarchical programs never collide in a program cache.
    #: A single process can force a simulated shape (e.g. 2x4 on 8 local
    #: devices); under jax.distributed the shape must match the real
    #: process layout.
    topology: Topology | None = None


#: the BSP carry's leaf names, in carry-tuple order — the frontier schema
#: shared by the segmented program, the host segment loop, and the
#: checkpoint mapping (ckpt/mining.py).  Per-miner scalars (sp, head, lam,
#: t, out_ptr, n_sig, work) ride [P] vectors host-side.
CARRY_FIELDS = (
    "occ_stack", "meta", "sp", "head", "hist", "hist_snap", "g_hist_acc",
    "hist2d", "lam", "t", "stats", "out_occ", "out_meta", "out_ptr",
    "n_sig", "trace", "work",
)


@dataclass
class MineOutput:
    hist: np.ndarray               # [N+2] global closed-set support histogram
    lam_final: int
    supersteps: int
    stats: dict[str, np.ndarray]   # per-device counters [P]
    sig_count: int = 0             # mode="test"
    sig_sup: np.ndarray | None = None
    sig_pos_sup: np.ndarray | None = None
    trace: SuperstepTrace | None = None  # decoded ring (trace_period > 0)
    hist2d: np.ndarray | None = None  # [N+1, Npos+1] (mode="count2d")
    # emitted pattern records (modes "test"/"count2d"; DESIGN.md §4):
    sig_occ: np.ndarray | None = None   # [K, W]u32 occurrence bitmaps
    sig_core: np.ndarray | None = None  # [K] core item of the emitting node
    emit_dropped: int = 0          # records lost to out_cap saturation
    trace_dropped: int = 0         # sampled trace records lost to ring wrap
    db_bits: np.ndarray | None = None  # [M, W]u32 packed DB (reused downstream)
    #: False when the pass stopped cooperatively at a superstep boundary
    #: (soft deadline) before draining the frontier — counts/records cover
    #: only the explored region (DESIGN.md §11)
    complete: bool = True


def _thresholds_int(
    n: int, n_pos: int, alpha: float, statistic: str | None = "fisher"
) -> np.ndarray:
    """Integer Tarone support-increase table for the named statistic.

    statistic=None (closed-frequent: no test, static min_sup only) gets an
    all-INT_MAX table — lambda can never advance, and no mode that runs
    without a statistic reads it anyway.
    """
    if statistic is None:
        return np.full(n + 2, INT_MAX, dtype=np.int32)
    thr = get_statistic(statistic).count_thresholds(n, n_pos, alpha)
    out = np.minimum(np.floor(thr), float(INT_MAX)).astype(np.int64)
    out = out.astype(np.int32)
    out[0] = INT_MAX  # bucket 0 never drives lambda
    out[np.isinf(thr)] = INT_MAX
    return out


@dataclass(frozen=True)
class PackedProblem:
    """A transaction database packed once, padded to program (bucket) dims.

    The core-level prepared input: `repro.api.Dataset` wraps one of these
    (adding labels, item names, and the bucket policy), and `mine()` builds
    an exact-fit instance per call.  Padded items/words/positives are zero
    bits, so they have zero support and can never be accepted, counted,
    emitted, or generate children — results are invariant to the padding
    (DESIGN.md §5).

    The database is carried as one item-tiled `BitmapLayout` (DESIGN.md §8):
    `db_tiles` [T, m_tile, W] is what the device program takes, `db_bits`
    [m_pad, W] is its free item-major reshape for host-side code.  `m_pad`
    (the program item dim) always equals `layout.m_pad` == T * m_tile.
    """

    layout: BitmapLayout   # item-tiled packed DB; layout.m_pad == m_pad
    pos_mask: np.ndarray   # [w_pad] u32 positive-transaction bitmap
    occ0: np.ndarray       # [w_pad] u32 root occurrence (all actual transactions)
    n: int                 # actual transactions
    n_pos: int             # actual positives
    m: int                 # actual items
    n_pad: int             # bucket transactions (program dim)
    npos_pad: int          # bucket positives (program dim)
    m_pad: int             # bucket items, tile-aligned (program dim)
    has_labels: bool = True

    def __post_init__(self):
        if self.layout.m_pad != self.m_pad:
            raise ValueError(
                f"m_pad={self.m_pad} != layout.m_pad={self.layout.m_pad}"
            )

    @property
    def db_tiles(self) -> np.ndarray:
        """[T, m_tile, w_pad] — the device program's database argument."""
        return self.layout.tiles

    @property
    def db_bits(self) -> np.ndarray:
        """[m_pad, w_pad] item-major view (host-side: root deal, closures)."""
        return self.layout.flat

    @property
    def m_tile(self) -> int:
        return self.layout.m_tile

    @property
    def w_pad(self) -> int:
        return self.layout.w


def pack_problem(
    db_bool: np.ndarray,
    labels: np.ndarray | None = None,
    *,
    n_pad: int | None = None,
    npos_pad: int | None = None,
    m_pad: int | None = None,
    m_tile: int | None = None,
) -> PackedProblem:
    """Pack the bool matrix exactly once, padding to the given program dims.

    Defaults pad to the exact dataset shape (the legacy one-shot path);
    `repro.api.Dataset` passes its shape-bucket dims so same-bucket datasets
    produce identically-shaped arguments and share compiled programs.

    `m_tile` caps the item-tile width (default `DEFAULT_ITEM_TILE`): the
    item dim is rounded up to a tile multiple when it exceeds one tile, and
    the program item dim becomes that tile-aligned extent.
    """
    db_bool = np.asarray(db_bool, dtype=bool)
    n, m = db_bool.shape
    if labels is not None:
        labels = np.asarray(labels, dtype=bool)
        n_pos = int(labels.sum())
    else:
        n_pos = max(1, n // 2)
    n_pad = n if n_pad is None else n_pad
    npos_pad = n_pos if npos_pad is None else npos_pad
    m_pad = m if m_pad is None else m_pad
    if n_pad < n or npos_pad < n_pos or m_pad < m:
        raise ValueError(
            f"bucket dims ({n_pad}, {npos_pad}, {m_pad}) smaller than dataset "
            f"({n}, {n_pos}, {m})"
        )
    packed = pack_db(db_bool)  # [m, w]
    return pack_problem_from_bits(
        packed, labels, n=n, n_pad=n_pad, npos_pad=npos_pad, m_pad=m_pad,
        m_tile=m_tile, n_pos=n_pos,
    )


def pack_problem_from_bits(
    db_bits: np.ndarray,
    labels: np.ndarray | None = None,
    *,
    n: int,
    n_pad: int | None = None,
    npos_pad: int | None = None,
    m_pad: int | None = None,
    m_tile: int | None = None,
    n_pos: int | None = None,
) -> PackedProblem:
    """`pack_problem` for an already word-packed [M, W] database.

    The paper-scale entry (data/synthetic.py generates alz_rec_30 straight
    into packed words — a dense [n, m] bool intermediate would be ~91 GB of
    float draws upstream): no repacking, just zero-pad into the tiled layout.
    `n` (actual transactions) cannot be recovered from packed words, so it
    is required; `n_pos` defaults from `labels` (or n // 2 unlabeled).
    """
    db_bits = np.asarray(db_bits, dtype=np.uint32)
    m, w = db_bits.shape
    if labels is not None:
        labels = np.asarray(labels, dtype=bool)
        if n_pos is None:
            n_pos = int(labels.sum())
    elif n_pos is None:
        n_pos = max(1, n // 2)
    n_pad = n if n_pad is None else n_pad
    npos_pad = n_pos if npos_pad is None else npos_pad
    m_pad = m if m_pad is None else m_pad
    w_pad = num_words(n_pad)
    if w > w_pad:
        raise ValueError(f"db_bits has {w} words but n_pad={n_pad} fits {w_pad}")
    max_tile = DEFAULT_ITEM_TILE if m_tile is None else m_tile
    m_pad, tile = item_tiling(max(m_pad, 1), max_tile)

    padded = np.zeros((m, w_pad), dtype=np.uint32)
    padded[:, :w] = db_bits
    layout = BitmapLayout.from_db_bits(padded, m=m, m_tile=tile, m_pad=m_pad)
    pos_mask = np.zeros(w_pad, dtype=np.uint32)
    if labels is not None:
        pos_bits = pack_db(labels[:, None])[0]
        pos_mask[: pos_bits.shape[0]] = pos_bits
    occ0 = np.zeros(w_pad, dtype=np.uint32)
    root = full_occ(n)
    occ0[: root.shape[0]] = root
    for arr in (pos_mask, occ0):
        arr.flags.writeable = False
    return PackedProblem(
        layout=layout,
        pos_mask=pos_mask,
        occ0=occ0,
        n=n, n_pos=n_pos, m=m,
        n_pad=n_pad, npos_pad=npos_pad, m_pad=m_pad,
        has_labels=labels is not None,
    )


def deal_roots(packed: PackedProblem, n_proc: int, cfg: EngineConfig, min_sup: int = 1):
    """Paper §4.5: expand the root on the host, deal depth-1 nodes round-robin.

    Returns (init_occ [P,CAP,W], init_meta [P,CAP,4], init_sp [P]).
    """
    db_bits, occ0 = packed.db_bits, packed.occ0
    s = supports_np(occ0, db_bits)            # padded items have s == 0
    in_clo = s == packed.n
    cand = np.flatnonzero((~in_clo) & (s >= max(1, min_sup)))
    clo_cum = np.concatenate([[0], np.cumsum(in_clo)])  # clo_cum[e] = |clo ∩ [0,e)|

    cap = cfg.stack_cap
    init_occ = np.zeros((n_proc, cap, packed.w_pad), dtype=np.uint32)
    init_meta = np.zeros((n_proc, cap, 4), dtype=np.int32)
    init_sp = np.zeros(n_proc, dtype=np.int32)
    for e in cand:
        p = int(e) % n_proc  # the paper's  i mod P = p_i  assignment
        sp = init_sp[p]
        assert sp < cap, "stack_cap too small for depth-1 preprocess"
        init_occ[p, sp] = occ0 & db_bits[e]
        init_meta[p, sp] = (e, clo_cum[e], s[e], 0)
        init_sp[p] = sp + 1
    return init_occ, init_meta, init_sp


def build_mine_step(
    *, n: int, n_pos: int, m: int, cfg: EngineConfig,
    schedule: LifelineSchedule, mode: str, axis=MINERS_AXIS,
    statistic: str | None = "fisher",
):
    """Wire the superstep phases into the per-device BSP program body.

    `n`/`n_pos`/`m` are program (shape-bucket) dims; the dataset's actual
    transaction/positive counts are runtime scalar arguments of the returned
    program, so one compiled program serves every same-bucket dataset.
    `statistic` names the registered test whose device P-value gates
    emission in modes "test"/"count2d" (None = emit every counted closed
    set); it is traced into the program, so fisher/chi2/None programs are
    distinct compilation artifacts.
    """
    if cfg.trace_period < 0:
        raise ValueError(f"trace_period must be >= 0, got {cfg.trace_period}")
    if cfg.trace_period and cfg.trace_cap <= 0:
        raise ValueError(
            "trace_period > 0 requires trace_cap > 0 (the ring needs slots); "
            "RuntimeConfig.resolve() defaults the cap when only the period "
            "is set"
        )
    NB = n + 2
    NB2 = (n + 1) * (n_pos + 1) if mode == "count2d" else 1
    # lambda-sync state (last-synced global hist + local snapshot) only
    # exists in mode "lamp1"; other modes carry 1-element dummies
    SNB = NB if mode == "lamp1" else 1
    n_proc = schedule.n_proc
    expand = build_expand(n=n, n_pos=n_pos, m=m, cfg=cfg, mode=mode,
                          statistic=statistic)
    steal_round = build_steal_round(schedule, cfg, axis)
    global_sync = build_global_sync(
        nb=NB, mode=mode, sync_period=cfg.sync_period, axis=axis
    )

    def body(carry, db_tiles, pos_mask, thr, delta, n_act, npos_act):
        (occ_stack, meta, sp, head, hist, hist_snap, g_hist_acc, hist2d, lam,
         t, stats, out_occ, out_meta, out_ptr, n_sig, trace, _work) = carry
        stats_before = stats
        # each part of the superstep runs under a named scope (expand,
        # steal, trace, sync), so every op of the compiled program names its
        # part in its metadata op_name and a device profile's time can be
        # attributed to the parts
        with jax.named_scope("expand"):
            (occ_stack, meta, sp, hist, hist2d, stats, out_occ, out_meta,
             out_ptr, sig_cnt) = expand(
                occ_stack, meta, sp, head, hist, hist2d, lam, stats, db_tiles,
                pos_mask, out_occ, out_meta, out_ptr, delta, n_act, npos_act,
            )
            n_sig = n_sig + sig_cnt
        with jax.named_scope("steal"):
            # the [P]-int hunger census: REQUEST side of the steal exchange,
            # gate for its payload ppermute, and the exact termination test
            # (steals only redistribute; they cannot turn an all-empty
            # superstep into work)
            hungry_vec = hunger_census(sp, n_proc, axis)
            n_hungry = jnp.sum(hungry_vec)
            if cfg.steal_enabled:
                occ_stack, meta, sp, head, got, gave, k_given, k_recv = (
                    steal_round(t, hungry_vec, n_hungry, occ_stack, meta, sp,
                                head))
                stats = stats.at[Stat.STEALS_GOT].add(got)
                stats = stats.at[Stat.GIVES].add(gave)
                stats = stats.at[Stat.STOLEN_NODES].add(k_given)
                stats = stats.at[Stat.STEAL_ROUNDS].add(
                    (n_hungry > 0).astype(jnp.int32)
                )
            else:
                k_given = k_recv = jnp.int32(0)
        with jax.named_scope("sync"):  # the superstep's own counters
            stats = stats.at[Stat.IDLE_STEPS].add((sp == 0).astype(jnp.int32))
            stats = stats.at[Stat.SUPERSTEPS].add(1)

        if cfg.trace_period:
            with jax.named_scope("trace"):
                # record *before* global_sync so LAMBDA is the value in
                # force during this superstep's expand; volumes are this-
                # step stat deltas.  Unsampled steps write to slot ==
                # trace_cap, which mode="drop" discards — no branch, no
                # psum, one 11-int store.
                deltas = stats - stats_before
                fired = (n_hungry > 0) & bool(cfg.steal_enabled)
                rec = jnp.stack([
                    t,                           # TraceField.STEP
                    lam,                         # TraceField.LAMBDA
                    sp,                          # TraceField.DEPTH
                    n_hungry,                    # TraceField.HUNGRY
                    fired.astype(jnp.int32),     # TraceField.FIRED
                    deltas[Stat.POPPED],         # TraceField.POPPED
                    deltas[Stat.PUSHED],         # TraceField.PUSHED
                    deltas[Stat.CLOSED],         # TraceField.CLOSED
                    sig_cnt,                     # TraceField.EMITTED
                    k_given,                     # TraceField.DONATED
                    k_recv,                      # TraceField.RECEIVED
                ]).astype(jnp.int32)
                sampled = (t % cfg.trace_period) == 0
                idx = t // cfg.trace_period
                slot = jnp.where(sampled, idx % cfg.trace_cap, cfg.trace_cap)
                trace = trace.at[slot].set(rec, mode="drop")
                stats = stats.at[Stat.TRACE_DROPPED].add(
                    (sampled & (idx >= cfg.trace_cap)).astype(jnp.int32)
                )

        with jax.named_scope("sync"):  # lambda sync, termination count
            lam, g_hist_acc, hist_snap = global_sync(
                t, hist, hist_snap, g_hist_acc, lam, thr
            )
            work = jnp.int32(n_proc) - n_hungry
            t = t + 1
        return (occ_stack, meta, sp, head, hist, hist_snap, g_hist_acc,
                hist2d, lam, t, stats, out_occ, out_meta, out_ptr, n_sig,
                trace, work)

    def program(init_occ, init_meta, init_sp, db_tiles, pos_mask, thr,
                lam0, delta, n_act, npos_act):
        # per-device views arrive with a leading length-1 shard axis
        occ_stack = init_occ[0]
        meta = init_meta[0]
        sp = init_sp[0]
        head = jnp.int32(0)
        w = occ_stack.shape[-1]
        hist = jnp.zeros(NB, jnp.int32)
        hist_snap = jnp.zeros(SNB, jnp.int32)
        g_hist_acc = jnp.zeros(SNB, jnp.int32)
        hist2d = jnp.zeros(NB2, jnp.int32)
        stats = jnp.zeros(_NSTAT, jnp.int32)
        out_occ = jnp.zeros((cfg.out_cap, w), jnp.uint32)
        out_meta = jnp.zeros((cfg.out_cap, 3), jnp.int32)
        out_ptr = jnp.int32(0)
        n_sig = jnp.int32(0)
        t = jnp.int32(0)
        # the superstep trace ring ([trace_cap, N_FIELDS] i32 per miner);
        # a 1-slot dummy keeps the carry structure static when tracing is off
        trace = jnp.zeros((max(cfg.trace_cap, 1), N_FIELDS), jnp.int32)

        def cond_fn(carry):
            (_occ, _meta, _sp, _head, _hist, _snap, _ghist, _hist2d, _lam, t,
             _stats, _out_occ, _out_meta, _out_ptr, _n_sig, _trace,
             work) = carry
            # work (miners with non-empty stacks) was psum'd at the previous
            # superstep boundary:
            with jax.named_scope("sync"):
                return (work > 0) & (t < cfg.max_steps)  # exact BSP termination

        with jax.named_scope("steal"):
            work0 = jnp.int32(n_proc) - jnp.sum(hunger_census(sp, n_proc, axis))
        carry = (occ_stack, meta, sp, head, hist, hist_snap, g_hist_acc,
                 hist2d, lam0, t, stats, out_occ, out_meta, out_ptr, n_sig,
                 trace, work0)
        carry = lax.while_loop(
            cond_fn,
            lambda c: body(c, db_tiles, pos_mask, thr, delta, n_act, npos_act),
            carry,
        )
        (_, _, _, _, hist, _, _, hist2d, lam, t, stats, out_occ, out_meta,
         out_ptr, n_sig, trace, _) = carry
        # one exact full-histogram psum at termination (the in-loop lambda
        # only ever saw sync_period-stale deltas; postprocess replays the
        # recursion from this exact histogram)
        with jax.named_scope("sync"):
            g_hist = collectives.psum(hist, axis)
            g_hist2d = collectives.psum(hist2d, axis)  # once, at termination — not per step
            g_sig = collectives.psum(n_sig, axis)
        return (
            g_hist, lam, t, stats[None], out_occ[None], out_meta[None],
            out_ptr[None], g_sig, trace[None], g_hist2d,
        )

    def seg_program(occ_stack, meta, sp, head, hist, hist_snap, g_hist_acc,
                    hist2d, lam, t, stats, out_occ, out_meta, out_ptr, n_sig,
                    trace, work, db_tiles, pos_mask, thr, delta, n_act,
                    npos_act, t_stop):
        # the segmented (checkpointable) variant: the full carry is a
        # program *argument* (host round-trip every segment) and the loop
        # runs to the runtime bound t_stop instead of draining the frontier.
        # Per-miner leaves arrive with a leading length-1 shard axis;
        # per-miner scalars ride [P] vectors (so [1] per device).
        carry = tuple(
            x[0] for x in (occ_stack, meta, sp, head, hist, hist_snap,
                           g_hist_acc, hist2d, lam, t, stats, out_occ,
                           out_meta, out_ptr, n_sig, trace, work)
        )

        def cond_fn(carry):
            t, work = carry[9], carry[16]
            # work was psum'd at the previous boundary — uniform across
            # miners, so the loop exits in lockstep; t_stop is runtime data
            # (no recompile per segment)
            with jax.named_scope("sync"):
                return (work > 0) & (t < t_stop)

        carry = lax.while_loop(
            cond_fn,
            lambda c: body(c, db_tiles, pos_mask, thr, delta, n_act, npos_act),
            carry,
        )
        # no terminal psums here: the host sums the per-miner histograms
        # once the frontier drains (segments_raw_output) — int32 addition
        # commutes, so the result is bit-identical to the device psum
        return tuple(x[None] for x in carry)

    return seg_program if cfg.ckpt_period > 0 else program


def mesh_axis(mesh) -> "str | tuple":
    """The collective-axis argument for this mesh: one name, or the topo
    tuple ("hosts", "local") — what hunger_census/steal/psum thread through."""
    names = tuple(mesh.axis_names)
    return names if len(names) > 1 else names[0]


def make_mesh_and_schedule(cfg: EngineConfig, devices):
    """The (mesh, lifeline schedule) pair cfg.topology selects.

    Flat (topology=None): the classic 1-D miners mesh + one-level schedule.
    With a Topology: the 2-D [hosts, local] mesh + the hierarchical
    two-level schedule (repro.topo) — the device list must match the
    topology's P exactly.
    """
    n_proc = len(devices)
    if cfg.topology is None:
        return (
            collectives.make_miner_mesh(devices),
            build_schedule(n_proc, cfg.n_random_perms, cfg.seed),
        )
    from repro.topo.hierarchy import build_hierarchical_schedule

    if cfg.topology.n_proc != n_proc:
        raise ValueError(
            f"topology {cfg.topology} needs {cfg.topology.n_proc} devices, "
            f"got {n_proc}"
        )
    return (
        collectives.make_topo_mesh(cfg.topology, devices),
        build_hierarchical_schedule(cfg.topology, cfg.n_random_perms, cfg.seed),
    )


def phase_in_specs(cfg: EngineConfig, axis=MINERS_AXIS) -> tuple:
    """PartitionSpecs of the phase program's argument tuple, in order.

    `axis` is `mesh_axis(mesh)` — a tuple shards the miner dim over both
    topo axes.  Exposed so the multi-process bootstrap (repro.topo) can wrap
    host numpy arguments into identically-sharded global arrays.
    """
    s = P(axis)
    if cfg.ckpt_period > 0:
        # segmented: every carry leaf miner-sharded, then the static
        # operands db_tiles, pos_mask, thr, delta, n_act, npos_act, t_stop
        return tuple(s for _ in CARRY_FIELDS) + (P(),) * 7
    return (s, s, s) + (P(),) * 7


def phase_out_specs(cfg: EngineConfig, axis=MINERS_AXIS) -> tuple:
    """PartitionSpecs of the phase program's outputs, in order."""
    s = P(axis)
    if cfg.ckpt_period > 0:
        return tuple(s for _ in CARRY_FIELDS)
    return (P(), P(), P(), s, s, s, s, P(), s, P())


def build_phase_program(
    packed_dims: tuple[int, int, int],
    *,
    cfg: EngineConfig,
    schedule: LifelineSchedule,
    mesh,
    mode: str,
    statistic: str | None = "fisher",
):
    """shard_map'd (unjitted) BSP program for one engine pass.

    `packed_dims` = (n_pad, npos_pad, m_pad) — the program (bucket) dims.
    The returned callable takes the argument tuple built by
    `make_phase_args` and is what `repro.api.MinerSession` AOT-compiles and
    caches; `mine()` wraps it in a fresh `jax.jit` per call.  `statistic`
    reaches the traced emission test (modes "test"/"count2d" only), so it
    must join any cache key for those modes.

    The mesh decides the collective wiring: a 1-D miners mesh runs every
    round over its single axis (flat or hierarchical schedule alike); the
    2-D topo mesh requires a factorized (hierarchical) schedule and splits
    the census psum and per-round ppermutes across the two axes.
    """
    n_pad, npos_pad, m_pad = packed_dims
    axis = mesh_axis(mesh)
    program = build_mine_step(
        n=n_pad, n_pos=npos_pad, m=m_pad, cfg=cfg, schedule=schedule,
        mode=mode, axis=axis, statistic=statistic,
    )
    return collectives.shard_map(
        program,
        mesh=mesh,
        in_specs=phase_in_specs(cfg, axis),
        out_specs=phase_out_specs(cfg, axis),
    )


def make_phase_args(
    packed: PackedProblem,
    *,
    n_proc: int,
    cfg: EngineConfig,
    mode: str,
    alpha: float,
    min_sup: int,
    delta: float,
    statistic: str | None = "fisher",
):
    """Build the program argument tuple (and the postprocess context).

    Every array's shape/dtype is a function of (bucket dims, cfg, n_proc)
    only, so repeat queries on a warm compiled program always re-match its
    input signature exactly.  The statistic enters here as *runtime data*
    (its Tarone threshold table); its traced half lives in
    `build_phase_program`.

    Returns (args, ctx) with ctx = dict(thr, start_sup) for postprocess.
    """
    start_sup = min_sup if mode != "lamp1" else 1
    init_occ, init_meta, init_sp = deal_roots(packed, n_proc, cfg, start_sup)
    thr = _thresholds_int(packed.n, packed.n_pos, alpha, statistic)
    thr_pad = np.full(packed.n_pad + 2, INT_MAX, dtype=np.int32)
    thr_pad[: thr.shape[0]] = thr
    args = (
        init_occ, init_meta, init_sp,
        packed.db_tiles, packed.pos_mask, thr_pad,
        np.int32(start_sup), np.float32(delta),
        np.int32(packed.n), np.int32(packed.n_pos),
    )
    return args, dict(thr=thr_pad, start_sup=start_sup)


def init_carry(
    packed: PackedProblem,
    *,
    n_proc: int,
    cfg: EngineConfig,
    mode: str,
    init_occ: np.ndarray,
    init_meta: np.ndarray,
    init_sp: np.ndarray,
    start_sup: int,
) -> dict[str, np.ndarray]:
    """Host-side initial BSP carry for the segmented program.

    A dict keyed by CARRY_FIELDS, every leaf a global [P, ...] numpy array
    (per-miner scalars as [P] vectors).  Mirrors exactly what the classic
    program initialises on-device before its while loop, including the
    boundary-census `work` the loop cond reads.
    """
    NB = packed.n_pad + 2
    SNB = NB if mode == "lamp1" else 1
    NB2 = (packed.n_pad + 1) * (packed.npos_pad + 1) if mode == "count2d" else 1
    w = init_occ.shape[-1]
    i32, P_ = np.int32, n_proc
    return {
        "occ_stack": np.ascontiguousarray(init_occ),
        "meta": np.ascontiguousarray(init_meta),
        "sp": np.ascontiguousarray(init_sp),
        "head": np.zeros(P_, i32),
        "hist": np.zeros((P_, NB), i32),
        "hist_snap": np.zeros((P_, SNB), i32),
        "g_hist_acc": np.zeros((P_, SNB), i32),
        "hist2d": np.zeros((P_, NB2), i32),
        "lam": np.full(P_, start_sup, i32),
        "t": np.zeros(P_, i32),
        "stats": np.zeros((P_, _NSTAT), i32),
        "out_occ": np.zeros((P_, cfg.out_cap, w), np.uint32),
        "out_meta": np.zeros((P_, cfg.out_cap, 3), i32),
        "out_ptr": np.zeros(P_, i32),
        "n_sig": np.zeros(P_, i32),
        "trace": np.zeros((P_, max(cfg.trace_cap, 1), N_FIELDS), i32),
        # miners with non-empty stacks — the same census the classic program
        # computes on-device before entering its loop
        "work": np.full(P_, int((np.asarray(init_sp) > 0).sum()), i32),
    }


def make_program_args(
    packed: PackedProblem,
    *,
    n_proc: int,
    cfg: EngineConfig,
    mode: str,
    alpha: float,
    min_sup: int,
    delta: float,
    statistic: str | None = "fisher",
):
    """`make_phase_args`, shaped for whichever program variant cfg selects.

    ckpt_period == 0: identical to `make_phase_args`.  ckpt_period > 0: the
    args tuple matches the segmented program's signature — carry leaves in
    CARRY_FIELDS order, then the static operands, then a t_stop placeholder
    — and ctx gains `carry0` (the initial carry dict) and `static` (the
    operands `run_segments` re-passes every dispatch).
    """
    args, ctx = make_phase_args(
        packed, n_proc=n_proc, cfg=cfg, mode=mode, alpha=alpha,
        min_sup=min_sup, delta=delta, statistic=statistic,
    )
    if cfg.ckpt_period <= 0:
        return args, ctx
    carry0 = init_carry(
        packed, n_proc=n_proc, cfg=cfg, mode=mode,
        init_occ=args[0], init_meta=args[1], init_sp=args[2],
        start_sup=ctx["start_sup"],
    )
    # db_tiles, pos_mask, thr / delta, n_act, npos_act — lam0 (args[6])
    # rides the carry instead
    static = args[3:6] + args[7:10]
    seg_args = tuple(carry0[k] for k in CARRY_FIELDS) + static + (np.int32(0),)
    ctx = dict(ctx, carry0=carry0, static=static)
    return seg_args, ctx


def run_segments(
    dispatch,
    carry: dict[str, np.ndarray],
    *,
    cfg: EngineConfig,
    static: tuple,
    should_stop=None,
    on_segment=None,
):
    """Host loop driving the segmented program to frontier exhaustion.

    Each iteration runs one ckpt_period-superstep segment on device, pulls
    the carry back to host, fires the engine.superstep fault point, then
    hands the carry to `on_segment` (the checkpoint writer) — in that order,
    so an injected death loses the running segment's checkpoint, the
    harshest recovery case.  `should_stop` is polled at the loop bottom
    only: a cooperative stop always has at least one segment of progress
    behind it, so a partial result is never empty-by-construction.

    Returns (carry, partial).
    """
    from repro.testing import faults

    partial = False
    while int(carry["work"][0]) > 0 and int(carry["t"][0]) < cfg.max_steps:
        t_stop = min(int(carry["t"][0]) + cfg.ckpt_period, cfg.max_steps)
        raw = dispatch(
            *(carry[k] for k in CARRY_FIELDS), *static, np.int32(t_stop)
        )
        carry = {k: np.asarray(v) for k, v in zip(CARRY_FIELDS, raw)}
        faults.check("engine.superstep", t=int(carry["t"][0]))
        if on_segment is not None:
            on_segment(carry)
        if (
            should_stop is not None
            and int(carry["work"][0]) > 0
            and int(carry["t"][0]) < cfg.max_steps
            and should_stop()
        ):
            partial = True
            break
    return carry, partial


def segments_raw_output(carry: dict[str, np.ndarray]):
    """Terminal carry -> the classic program's 10-tuple raw output.

    The host stands in for the classic program's termination psums; int32
    addition commutes (mod 2^32), so the sums are bit-identical to the
    device reduction regardless of miner count or summation order.
    """
    g_hist = carry["hist"].sum(axis=0, dtype=np.int32)
    g_hist2d = carry["hist2d"].sum(axis=0, dtype=np.int32)
    g_sig = carry["n_sig"].sum(dtype=np.int32)
    return (
        g_hist, carry["lam"][0], carry["t"][0], carry["stats"],
        carry["out_occ"], carry["out_meta"], carry["out_ptr"], g_sig,
        carry["trace"], g_hist2d,
    )


def postprocess_phase(
    raw_out,
    *,
    packed: PackedProblem,
    n_proc: int,
    cfg: EngineConfig,
    mode: str,
    thr: np.ndarray,
    start_sup: int,
    delta: float,
    statistic: str | None = "fisher",
    partial: bool = False,
    schedule: LifelineSchedule | None = None,
) -> MineOutput:
    """Device output -> MineOutput: slice padding, fold in the root closed
    set, gather emitted pattern records, surface overflow.  `statistic`
    must match the program's: the root closed set never transits the device
    buffers, so its significance is re-decided host-side with the same test
    (or counted unconditionally when statistic is None — closed-frequent).
    `schedule` (when given) keys the decoded trace's per-round/per-tier
    steal attribution by the round names the pass actually cycled."""
    n, n_pos = packed.n, packed.n_pos
    root_sup = n  # support of the root closure == all transactions
    (g_hist, lam, t, stats, out_occ, out_meta, out_ptr, g_sig, trace,
     g_hist2d) = jax.tree.map(np.asarray, raw_out)
    # count the root closed set (clo of the empty itemset), support = N
    g_hist = g_hist.copy()
    if root_sup >= start_sup:
        g_hist[root_sup] += 1
        if mode == "lamp1":
            # replay the lambda recursion including the root contribution
            lam = int(recompute_lambda(g_hist, thr, int(lam), xp=np))
    # bucket padding (hist bins past n+1 are structurally zero) is an
    # implementation detail — slice back to the dataset's exact shape
    g_hist = g_hist[: n + 2]

    stats_dict = {name: stats[:, i] for i, name in enumerate(STAT_NAMES)}
    if np.any(stats_dict["overflow"]):
        raise RuntimeError("stack overflow in engine: increase stack_cap/push_cap")
    # a cooperative (soft-deadline) stop legitimately leaves the frontier
    # undrained — only an *uninterrupted* pass hitting max_steps is an error
    if not partial and int(t) >= cfg.max_steps:
        raise RuntimeError("engine hit max_steps before termination")

    sig_sup = sig_pos = sig_occ = sig_core = None
    n_sig = int(g_sig)
    emit_dropped = int(stats_dict["emit_dropped"].sum())
    if mode in ("test", "count2d"):
        # cross-device gather of the emitted pattern records: one boolean
        # mask over the flattened [P * out_cap] record axis, device-major —
        # identical order to the old per-device slice-and-concat loop
        ptrs = out_ptr.reshape(-1)
        live = (np.arange(cfg.out_cap)[None, :] < ptrs[:, None]).reshape(-1)
        sig_occ = out_occ.reshape(n_proc * cfg.out_cap, -1)[live]
        allmeta = out_meta.reshape(n_proc * cfg.out_cap, 3)[live]
        sig_core, sig_sup, sig_pos = allmeta[:, 0], allmeta[:, 1], allmeta[:, 2]
        if emit_dropped:
            warnings.warn(
                f"pattern emission overflow: {emit_dropped} significant records "
                f"dropped (out_cap={cfg.out_cap} saturated); counts stay exact "
                "but the emitted pattern set is incomplete — raise "
                "EngineConfig.out_cap",
                RuntimeWarning,
                stacklevel=3,
            )
    if mode == "test":
        # root significance (host-side, same test as on device)
        if statistic is None:
            # closed-frequent objective: the root closed set counts whenever
            # it clears the support threshold — there is no test to fail
            if root_sup >= start_sup:
                n_sig += 1
        elif root_sup >= start_sup and packed.has_labels:
            p_root = get_statistic(statistic).pvalue(root_sup, n_pos, n, n_pos)[0]
            if p_root <= delta:
                n_sig += 1

    hist2d = None
    if mode == "count2d":
        hist2d = g_hist2d.reshape(packed.n_pad + 1, packed.npos_pad + 1)
        hist2d = hist2d[: n + 1, : n_pos + 1].copy()
        if root_sup >= start_sup:
            hist2d[root_sup if root_sup <= n else n, n_pos] += 1

    trace_dec = None
    trace_dropped = 0
    if cfg.trace_period:
        trace_dec = decode_trace(
            trace, supersteps=int(t), period=cfg.trace_period,
            round_names=schedule.names if schedule is not None else None,
            round_tiers=schedule.tiers if schedule is not None else None,
        )
        trace_dropped = trace_dec.dropped
        if trace_dropped:
            warnings.warn(
                f"superstep trace ring wrapped: {trace_dropped} oldest "
                f"sampled records overwritten (trace_cap={cfg.trace_cap}, "
                f"trace_period={cfg.trace_period}, {int(t)} supersteps); "
                "the decoded timeline covers only the most recent window — "
                "raise trace_cap or trace_period",
                RuntimeWarning,
                stacklevel=3,
            )
    return MineOutput(
        hist=g_hist,
        lam_final=int(lam),
        supersteps=int(t),
        stats=stats_dict,
        sig_count=n_sig,
        sig_sup=sig_sup,
        sig_pos_sup=sig_pos,
        trace=trace_dec,
        hist2d=hist2d,
        sig_occ=sig_occ,
        sig_core=sig_core,
        emit_dropped=emit_dropped,
        trace_dropped=trace_dropped,
        db_bits=packed.db_bits,
        complete=not partial,
    )


def mine(
    db_bool: np.ndarray,
    labels: np.ndarray | None = None,
    *,
    mode: str = "lamp1",
    alpha: float = 0.05,
    min_sup: int = 1,
    delta: float = 0.0,
    cfg: EngineConfig = EngineConfig(),
    devices=None,
    packed: PackedProblem | None = None,
    statistic: str | None = "fisher",
    ckpt_dir: str | None = None,
    resume_from: str | None = None,
    should_stop=None,
    ckpt_keep: int = 3,
) -> MineOutput:
    """Run one engine pass over all (or the given) local devices.

    The one-shot low-level entry: packs the database (unless a prepared
    `packed` is given), compiles the phase program for this call, runs it,
    and postprocesses.  For repeated queries use `repro.api.MinerSession`,
    which caches compiled programs across phases, queries, and same-bucket
    datasets.

    With `cfg.ckpt_period > 0` the pass runs segmented (DESIGN.md §11):
    `ckpt_dir` checkpoints the frontier every segment, `resume_from`
    restores the newest valid step (elastically resharded onto this call's
    device count), and `should_stop()` polled at segment boundaries stops
    the pass cooperatively (MineOutput.complete=False).
    """
    if mode not in VALID_MODES:
        raise ValueError(
            f"unknown engine mode {mode!r}; valid modes: {', '.join(VALID_MODES)}"
        )
    if (ckpt_dir or resume_from or should_stop is not None) and cfg.ckpt_period <= 0:
        raise ValueError(
            "ckpt_dir/resume_from/should_stop need the segmented program: "
            "set EngineConfig.ckpt_period > 0"
        )
    if packed is None:
        packed = pack_problem(db_bool, labels)
    if devices is None:
        devices = jax.devices()
    n_proc = len(devices)
    mesh, schedule = make_mesh_and_schedule(cfg, devices)

    args, ctx = make_program_args(
        packed, n_proc=n_proc, cfg=cfg, mode=mode, alpha=alpha,
        min_sup=min_sup, delta=delta, statistic=statistic,
    )
    shardy = build_phase_program(
        (packed.n_pad, packed.npos_pad, packed.m_pad),
        cfg=cfg, schedule=schedule, mesh=mesh, mode=mode, statistic=statistic,
    )
    fn = jax.jit(shardy)
    partial = False
    if cfg.ckpt_period > 0:
        from repro.ckpt import mining as ckpt_mining

        provenance = ckpt_mining.make_provenance(
            packed, mode=mode, statistic=statistic, alpha=alpha,
            start_sup=ctx["start_sup"], delta=delta,
        )
        carry = ctx["carry0"]
        if resume_from:
            restored = ckpt_mining.restore_frontier(
                resume_from, provenance=provenance, n_proc=n_proc, cfg=cfg,
                mode=mode,
            )
            if restored is not None:
                carry = restored
        on_segment = None
        if ckpt_dir:
            def on_segment(c):
                ckpt_mining.save_frontier(
                    c, ckpt_dir, provenance=provenance, keep=ckpt_keep
                )
        carry, partial = run_segments(
            fn, carry, cfg=cfg, static=ctx["static"],
            should_stop=should_stop, on_segment=on_segment,
        )
        raw = segments_raw_output(carry)
    else:
        raw = fn(*args)
    return postprocess_phase(
        raw, packed=packed, n_proc=n_proc, cfg=cfg, mode=mode,
        thr=ctx["thr"], start_sup=ctx["start_sup"], delta=delta,
        statistic=statistic, partial=partial, schedule=schedule,
    )


# ----------------------------------------------------- legacy public shim
def lamp_distributed(
    db_bool: np.ndarray,
    labels: np.ndarray,
    alpha: float = 0.05,
    cfg: EngineConfig = EngineConfig(),
    devices=None,
    fuse_phase23: bool = False,
    pipeline: str | None = None,
):
    """Deprecated one-shot LAMP entry — use `repro.api` instead.

    .. deprecated::
        The canonical surface is session-based::

            from repro.api import Dataset, MinerSession
            report = MinerSession().mine(Dataset.from_dense(db, labels))

        `MinerSession` compiles each phase program once and reuses it across
        phases, repeat queries, and same-bucket datasets; this shim rebuilds
        a fresh session per call (re-compiling every phase, exactly like the
        historical behavior) and flattens the typed `MineReport` back into
        the documented legacy dict: lambda_final, min_sup,
        correction_factor, delta, n_significant, results, phase_outputs.

    The phase staging is pluggable: `pipeline` names an entry in PIPELINES
    ("three_phase" | "fused23").  `fuse_phase23=True` is the backward-
    compatible alias for pipeline="fused23".
    """
    warnings.warn(
        "lamp_distributed() is deprecated: use repro.api.MinerSession.mine() "
        "on a repro.api.Dataset (compile-once sessions, typed MineReport)",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.api import (
        EXACT_BUCKETS, AlgorithmConfig, Dataset, MinerSession, RuntimeConfig,
    )
    from repro.api.session import PIPELINES as _pipelines

    if pipeline is None:
        pipeline = "fused23" if fuse_phase23 else "three_phase"
    elif fuse_phase23 and pipeline != "fused23":
        raise ValueError(
            f"fuse_phase23=True conflicts with pipeline={pipeline!r}"
        )
    if pipeline not in _pipelines:
        raise ValueError(
            f"unknown pipeline {pipeline!r}; available: {sorted(_pipelines)}"
        )
    # exact buckets: bit-for-bit the historical program shapes
    ds = Dataset.from_dense(db_bool, labels, bucket_policy=EXACT_BUCKETS)
    session = MinerSession(
        devices=devices,
        algorithm=AlgorithmConfig(alpha=alpha, pipeline=pipeline),
        runtime=RuntimeConfig.from_engine_config(cfg),
    )
    return session.mine(ds).to_legacy_dict()


def __getattr__(name: str):
    # PIPELINES moved to repro.api.session (imported lazily: api -> core is
    # the module-level direction; this back-compat alias must not cycle).
    if name == "PIPELINES":
        from repro.api.session import PIPELINES

        return PIPELINES
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
