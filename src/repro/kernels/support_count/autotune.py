"""Block-size autotuner for the support-count popcount-GEMM (DESIGN.md §8).

The kernel's block sizes used to be hard-coded `(8, 512, 32)` — tuned once
by hand for one toy shape.  Paper-scale problems span three decades of item
counts and word widths (Table 1: 11,914 x 22 words up to 250,120 x 12, plus
mcf7's 400-word transaction axis), and the right tiling moves with them.

Two layers, cheapest first:

  1. a *seed table* measured by `benchmarks/kernel_roofline.py` (or any
     caller of `measure_blocks`) and persisted as JSON — on load, a shape
     bucket that was measured wins outright;
  2. an *analytic* roofline fallback (the same VPU/HBM model the roofline
     benchmark reports): among power-of-two candidates that divide the
     bucket-padded dims, fit the VMEM budget and (for the TPU kernel) meet
     the lowering's (8, 128) block rule, minimize modeled time =
     padded word-ops / VPU throughput + HBM bytes / bandwidth + a per-grid-
     step overhead that penalizes tiny blocks; padding waste is priced in
     because the model runs on padded dims.

`choose_blocks` is deterministic for a given (shape bucket, impl, loaded
seed table), so a resolved `RuntimeConfig` — which folds the chosen triple
into the compiled-program cache key — stays stable across a session's life.
Point `REPRO_SC_AUTOTUNE` at a seed JSON (the artifact CI uploads) to carry
measured tunings across processes; a file that cannot be read is an error.
"""

from __future__ import annotations

import functools
import json
import os
import time

import numpy as np

__all__ = [
    "VMEM_BUDGET",
    "candidate_blocks",
    "choose_blocks",
    "clear_seed_table",
    "load_seed_table",
    "measure_blocks",
    "modeled_time_us",
    "save_seed_table",
    "tpu_blocks_ok",
    "vmem_bytes",
]

#: per-grid-step VMEM working set ceiling: half of a v5e core's 16 MiB so
#: the pipeline can double-buffer the next block's DMA behind the compute
VMEM_BUDGET = 8 * 2**20

# roofline constants (shared with benchmarks/kernel_roofline.py)
VPU_INT_OPS = 4.8e12   # v5e 8x128 lanes, ~940 MHz, 4 ALUs
HBM_BW = 819e9
GRID_STEP_US = 0.5     # modeled per-step dispatch/DMA-issue overhead

_ENV_SEED = "REPRO_SC_AUTOTUNE"

_CAND_B = (8, 16, 32, 64)
_CAND_M = (128, 256, 512, 1024, 2048)
_CAND_W = (8, 16, 32, 64, 128)
# GPU (triton lowering): smaller lane budget, shared-memory-sized blocks
_CAND_M_GPU = (64, 128, 256)
_CAND_W_GPU = (8, 16, 32)

_seed_rows: list[dict] = []


def vmem_bytes(bb: int, bm: int, bw: int) -> int:
    """Working set of one grid step: occ + db + out blocks + the [bb, bw, bm]
    popcount intermediate (all 4-byte words)."""
    return 4 * (bb * bw + bw * bm + bb * bm + bb * bw * bm)


def _pow2ceil(x: int, floor: int) -> int:
    out = floor
    while out < x:
        out *= 2
    return out


def bucket_dims(b: int, m: int, w: int) -> tuple[int, int, int]:
    """Power-of-two shape bucket (floors = smallest candidate blocks): the
    stable padded dims ragged caller shapes collapse onto, and the key the
    block choice (and therefore the jit cache) is a function of."""
    return _pow2ceil(b, 8), _pow2ceil(m, 128), _pow2ceil(w, 8)


def tpu_blocks_ok(blocks: tuple[int, int, int], wp: int) -> bool:
    """Whether the TPU lowering accepts these blocks for a `wp`-word sweep.

    Every block's last dim must be a multiple of 128 lanes or the whole
    axis, and its second-to-last a multiple of 8 sublanes: `bm` lies on
    lanes (db and out blocks), `bb` on sublanes (occ and out), and `bw` is
    the lane dim of the occ block `(bb, bw)`, so a `bw` below 128 must span
    all `wp` words.
    """
    bb, bm, bw = blocks
    return bb % 8 == 0 and bm % 128 == 0 and (bw == wp or bw % 128 == 0)


def candidate_blocks(b: int, m: int, w: int, impl: str = "pallas"):
    """Power-of-two (bb, bm, bw) triples that divide the bucketed dims and
    fit the VMEM budget; for the TPU kernel ("pallas" and its interpreter)
    only triples `tpu_blocks_ok` accepts."""
    bp, mp, wp = bucket_dims(b, m, w)
    cand_m = _CAND_M_GPU if impl == "pallas_gpu" else _CAND_M
    cand_w = _CAND_W_GPU if impl == "pallas_gpu" else _CAND_W
    tpu = impl in ("pallas", "pallas_interpret")
    out = []
    for bb in _CAND_B:
        if bb > bp:
            continue
        for bm in cand_m:
            if bm > mp:
                continue
            for bw in cand_w:
                if bw > wp or (tpu and not tpu_blocks_ok((bb, bm, bw), wp)):
                    continue
                if vmem_bytes(bb, bm, bw) <= VMEM_BUDGET:
                    out.append((bb, bm, bw))
    # never empty: bucket_dims floors every dim at a candidate that fits
    return out


def modeled_time_us(b: int, m: int, w: int, blocks: tuple[int, int, int]) -> float:
    """Analytic roofline time for one full [B, M, W] sweep at these blocks.

    Runs on *bucket-padded* dims, so block choices that force more padding
    pay for it; the per-grid-step term penalizes shredding the sweep into
    tiny blocks (each step re-issues DMA and loop control).
    """
    bb, bm, bw = blocks
    bp, mp, wp = bucket_dims(b, m, w)
    bp = -(-bp // bb) * bb
    mp = -(-mp // bm) * bm
    wp = -(-wp // bw) * bw
    words = bp * mp * wp
    int_ops = 3 * words  # AND + popcount + accumulate
    # db streams once per b-block row; occ + out are small in comparison
    bytes_hbm = (bp // bb) * (wp * mp * 4) + (bp * wp + bp * mp) * 4
    steps = (bp // bb) * (mp // bm) * (wp // bw)
    return (int_ops / VPU_INT_OPS + bytes_hbm / HBM_BW) * 1e6 + steps * GRID_STEP_US


def _seed_lookup(b: int, m: int, w: int, impl: str):
    key = bucket_dims(b, m, w)
    best = None
    for row in _seed_rows:
        if row.get("impl", "pallas") != impl:
            continue
        if tuple(row["bucket"]) != key:
            continue
        if best is None or row["time_us"] < best["time_us"]:
            best = row
    return tuple(best["blocks"]) if best else None


@functools.lru_cache(maxsize=512)
def _choose(b: int, m: int, w: int, impl: str, seed_gen: int):
    seeded = _seed_lookup(b, m, w, impl)
    if seeded is not None:
        return seeded
    cands = candidate_blocks(b, m, w, impl)
    return min(
        cands,
        key=lambda blk: (modeled_time_us(b, m, w, blk), -blk[1], -blk[2]),
    )


_seed_gen = 0  # bumped on table load so the lru cache can't serve stale picks


def choose_blocks(b: int, m: int, w: int, impl: str = "pallas") -> tuple[int, int, int]:
    """The (block_b, block_m, block_w) triple for a [B, W] x [M, W] sweep.

    Deterministic per (shape bucket, impl, loaded seed table); the blocks
    always divide the power-of-two bucket of each dim, so callers that pad
    to `bucket_dims` never need per-block re-padding.
    """
    if impl == "ref":  # the jnp contraction has no blocks
        return (0, 0, 0)
    return _choose(*bucket_dims(b, m, w), impl, _seed_gen)


# ------------------------------------------------------------- seed table IO
def load_seed_table(path: str) -> int:
    """Load measured rows ({impl, bucket, blocks, time_us}); returns count."""
    global _seed_gen
    with open(path) as f:
        rows = json.load(f)
    rows = rows["rows"] if isinstance(rows, dict) else rows
    for row in rows:  # a malformed row fails here, not at dispatch
        if len(row["bucket"]) != 3 or len(row["blocks"]) != 3:
            raise ValueError(f"seed row needs 3-dim bucket and blocks: {row}")
        if row.get("impl", "pallas") == "pallas" and not tpu_blocks_ok(
                tuple(row["blocks"]), row["bucket"][2]):
            raise ValueError(f"seed row has blocks the TPU refuses: {row}")
        float(row["time_us"])
    _seed_rows.extend(rows)
    _seed_gen += 1
    _choose.cache_clear()
    return len(_seed_rows)


def clear_seed_table() -> None:
    global _seed_gen
    _seed_rows.clear()
    _seed_gen += 1
    _choose.cache_clear()


def save_seed_table(path: str, rows: list[dict]) -> str:
    with open(path, "w") as f:
        json.dump({"suite": "support-count-autotune", "rows": rows}, f, indent=1)
        f.write("\n")
    return path


def _maybe_load_env() -> None:
    path = os.environ.get(_ENV_SEED)
    if not path:
        return
    try:
        load_seed_table(path)
    except (OSError, ValueError, KeyError, TypeError) as e:
        raise RuntimeError(
            f"{_ENV_SEED}={path!r}: cannot load the support-count seed table "
            f"({type(e).__name__}: {e}); fix the file or unset the variable"
        ) from e


_maybe_load_env()


# ------------------------------------------------------------------ measure
def measure_blocks(
    b: int,
    m: int,
    w: int,
    *,
    impl: str = "auto",
    iters: int = 3,
    max_candidates: int = 8,
    seed: int = 0,
) -> list[dict]:
    """Time the top analytic candidates on the active backend.

    Returns seed-table rows sorted fastest-first (feed to `save_seed_table`
    and later `load_seed_table` / `REPRO_SC_AUTOTUNE`).  On CPU this times
    the interpreted kernel — meaningless for TPU placement but a consistent
    ordering for CPU CI, which is where pallas_interpret carries mines.
    """
    from .ops import resolve_impl, support_counts

    impl = resolve_impl(impl)
    rng = np.random.default_rng(seed)
    occ = rng.integers(0, 2**32, size=(b, w), dtype=np.uint32)
    db = rng.integers(0, 2**32, size=(m, w), dtype=np.uint32)
    cands = sorted(
        candidate_blocks(b, m, w, impl),
        key=lambda blk: modeled_time_us(b, m, w, blk),
    )[:max_candidates]
    rows = []
    for blk in cands:
        out = support_counts(occ, db, impl=impl, blocks=blk)  # compile
        np.asarray(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            np.asarray(support_counts(occ, db, impl=impl, blocks=blk))
        dt = (time.perf_counter() - t0) / iters
        rows.append({
            "impl": impl,
            "bucket": list(bucket_dims(b, m, w)),
            "shape": [b, m, w],
            "blocks": list(blk),
            "time_us": round(dt * 1e6, 2),
            "modeled_us": round(modeled_time_us(b, m, w, blk), 2),
            "vmem_kib": round(vmem_bytes(*blk) / 1024, 1),
        })
    rows.sort(key=lambda r: r["time_us"])
    return rows
