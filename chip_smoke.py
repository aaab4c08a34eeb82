#!/usr/bin/env python3
"""Chip smoke test: the miner's main path on one TPU chip, checked end to end.

    python chip_smoke.py               # phases A, B, C on one chip
    python chip_smoke.py --four-chips  # phase A's hapmap_dom_20 query on 4 chips vs 1

Drives the public entry points (`MinerSession.run`, `MiningService`) with the
Pallas support-count kernel on data generated from fixed seeds
(`repro.data.synthetic`), and checks every answer:

  A  Full Table-1 width through the item-tiled kernel.  hapmap_dom_20
     (11,914 items x 697 transactions, 4 item tiles) answers
     ClosedFrequentQuery(min_sup=625) with kernel "pallas" and again with
     "ref": the ResultSets must be bit-identical.  alz_rec_30 (250,120 x 364,
     64 tiles) runs one count phase at min_sup=347 with both kernels:
     identical support histograms and node counts.  Both thresholds sit in
     their instance's valley (benchmarks/bench_mining.py); below them the
     generator's near-universal item clique makes the lattice exponential.
  B  Full LAMP: SignificantPatternQuery(alpha=0.05, statistic="fisher") with
     the three_phase pipeline on hapmap_dom_10 at 8% of its items, against
     the sequential oracle `core.lamp.lamp`: itemsets, supports, lambda,
     min_sup, delta and P-values.
  C  Service: MiningService(size=1) warmed for phase B's bucket answers four
     queries (fisher and chi2, two seeds).  Each must equal a direct
     `session.run` of the same query, and none may compile.

With --four-chips only phase A's hapmap_dom_20 query runs, on a flat 1x4
mesh with stealing and on one chip: bit-identical ResultSets, every chip
pops work, and steals happen.

Every engine pass prints one line (wall and compile seconds, nodes,
supersteps, block triple, item tiles).  The last stdout line is
`{"ok": true, "device": {...}}`, printed only when every check passed.
Without a TPU (e.g. JAX_PLATFORMS=cpu) the script exits non-zero before any
phase.  It runs in one process and starts no other.  JAX's compilation cache
goes where `repro.launch.compile_cache` says.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import struct
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.api import (  # noqa: E402
    ClosedFrequentQuery,
    Dataset,
    MinerSession,
    RuntimeConfig,
    SignificantPatternQuery,
)
from repro.api.dataset import BucketPolicy  # noqa: E402
from repro.core.bitmap import DEFAULT_ITEM_TILE  # noqa: E402
from repro.core.lamp import lamp  # noqa: E402
from repro.data.synthetic import paper_problem, paper_problem_packed  # noqa: E402
from repro.serve import MiningService, WarmupSpec  # noqa: E402

#: the sizes the chip runs: (problem, scale_items[, min_sup]) per phase, and
#: the item-tile width of phase A's layout
FULL = {
    "wide": ("hapmap_dom_20", 1.0, 625),
    "tiled": ("alz_rec_30", 1.0, 347),
    "lamp": ("hapmap_dom_10", 0.08),
    "service_seeds": (1, 2),
    "item_tile": DEFAULT_ITEM_TILE,
}


class SmokeFailure(AssertionError):
    """A check of the smoke test failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ helpers
def packed_dataset(problem: str, scale_items: float, item_tile: int) -> Dataset:
    """A Table-1 problem generated straight into packed words (seed 0)."""
    db_bits, labels, planted, spec = paper_problem_packed(problem, scale_items)
    return Dataset.from_packed_words(
        db_bits, labels, n_transactions=spec.n_transactions, name=spec.name,
        planted=planted, bucket_policy=BucketPolicy(item_tile=item_tile),
    )


def dense_problem(problem: str, scale_items: float, seed: int = 0):
    """(db_bool, labels, Dataset) of a Table-1 problem, for the oracle."""
    db, labels, planted, spec = paper_problem(problem, scale_items, seed=seed)
    ds = Dataset.from_dense(db, labels, name=f"{spec.name}/seed{seed}",
                            planted=planted)
    return db, labels, ds


def make_session(devices, kernel: str) -> MinerSession:
    return MinerSession(devices, runtime=RuntimeConfig(kernel_impl=kernel))


def report_phases(tag: str, phases, kernel: str) -> None:
    """One line per engine pass; every pass must have run `kernel`."""
    for ph in phases:
        print(f"[{tag}] {ph.mode} kernel={ph.kernel_impl} wall_s={ph.wall_s} "
              f"compile_s={ph.compile_s} nodes={ph.n_nodes} "
              f"supersteps={ph.supersteps} blocks={ph.kernel_blocks} "
              f"tiles={ph.n_item_tiles}x{ph.item_tile or 'all'}", flush=True)
        check(ph.kernel_impl == kernel,
              f"{tag}: {ph.mode} ran kernel {ph.kernel_impl!r}, not {kernel!r}")


def result_key(report):
    """Everything a query answers, floats by their bits (NaN-safe)."""
    rs = report.results
    return (
        report.lambda_final, report.min_sup, report.correction_factor,
        struct.pack("<d", report.delta), report.n_significant, rs.complete,
        [(p.items, p.support, p.pos_support,
          struct.pack("<dd", p.pvalue, p.qvalue)) for p in rs],
    )


def check_same(tag: str, a, b, what: str) -> None:
    ka, kb = result_key(a), result_key(b)
    check(ka == kb, f"{tag}: {what} differ: {len(a.results)} vs "
                    f"{len(b.results)} patterns, n_significant "
                    f"{a.n_significant} vs {b.n_significant}")


# ------------------------------------------------------------------ phases
def phase_wide(devices, kernel: str, problem: str, scale_items: float,
               min_sup: int, item_tile: int):
    """A1: a full-width closed-frequent query, `kernel` vs "ref"."""
    ds = packed_dataset(problem, scale_items, item_tile)
    query = ClosedFrequentQuery(min_sup=min_sup)
    reports = {}
    for k in (kernel, "ref"):
        reports[k] = make_session(devices, k).run(ds, query)
        report_phases(f"A:{ds.name}:{k}", reports[k].phases, k)
    check_same(f"A:{ds.name}", reports[kernel], reports["ref"],
               f"{kernel} and ref ResultSets")
    check(len(reports[kernel].results) > 0, f"A:{ds.name}: no patterns")
    return ds, reports[kernel]


def phase_tiled(devices, kernel: str, problem: str, scale_items: float,
                min_sup: int, item_tile: int) -> None:
    """A2: one count phase over many item tiles, `kernel` vs "ref"."""
    ds = packed_dataset(problem, scale_items, item_tile)
    outs = {}
    for k in (kernel, "ref"):
        ph = make_session(devices, k).run_phase(ds, "count", min_sup=min_sup)
        report_phases(f"A:{ds.name}:{k}", [ph], k)
        outs[k] = ph
    a, b = outs[kernel], outs["ref"]
    check(a.output.hist.tolist() == b.output.hist.tolist(),
          f"A:{ds.name}: count histograms differ")
    check(a.n_nodes == b.n_nodes, f"A:{ds.name}: nodes {a.n_nodes} vs {b.n_nodes}")
    check(a.n_item_tiles > 1, f"A:{ds.name}: expected several item tiles")


def phase_lamp(devices, kernel: str, problem: str, scale_items: float,
               alpha: float = 0.05):
    """B: full LAMP against the sequential oracle; returns the warm session."""
    db, labels, ds = dense_problem(problem, scale_items)
    session = make_session(devices, kernel)
    rep = session.run(ds, SignificantPatternQuery(
        alpha=alpha, statistic="fisher", pipeline="three_phase"))
    report_phases(f"B:{ds.name}", rep.phases, kernel)
    ref = lamp(db, labels, alpha=alpha)
    tag = f"B:{ds.name}"
    for name, got, want in [
        ("lambda", rep.lambda_final, ref.lambda_final),
        ("min_sup", rep.min_sup, ref.min_sup),
        ("k", rep.correction_factor, ref.correction_factor),
        ("delta", rep.delta, ref.delta),
        ("n_significant", rep.n_significant, len(ref.significant)),
    ]:
        check(got == want, f"{tag}: {name} {got!r} != oracle {want!r}")
    got = sorted((p.items, p.support, p.pos_support, p.pvalue)
                 for p in rep.results)
    want = sorted((tuple(sorted(s.items)), s.support, s.pos_support, s.pvalue)
                  for s in ref.significant)
    diff = sorted(set(got) ^ set(want))
    check(not diff, f"{tag}: {len(diff)} patterns differ from the oracle "
                    f"(delta={ref.delta!r}), first: {diff[:3]}")
    check(len(got) > 0, f"{tag}: nothing significant")
    return ds, session


def phase_service(devices, kernel: str, problem: str, scale_items: float,
                  seeds, direct: MinerSession) -> None:
    """C: four served queries, each equal to a direct run, none compiling."""
    work = []
    for seed in seeds:
        _, _, ds = dense_problem(problem, scale_items, seed=seed)
        for stat in ("fisher", "chi2"):
            work.append((ds, SignificantPatternQuery(alpha=0.05, statistic=stat)))
    bucket = work[0][0].bucket
    check(all(ds.bucket == bucket for ds, _ in work),
          "C: the seeds landed in different buckets")
    service = MiningService(
        size=1, devices=devices, runtime=RuntimeConfig(kernel_impl=kernel),
        warmups=[WarmupSpec(bucket, statistic=s) for s in ("fisher", "chi2")],
    )

    async def drive():
        compiled = await service.start()
        try:
            results = await asyncio.gather(
                *(service.mine(ds, q, client=f"c{i}")
                  for i, (ds, q) in enumerate(work)))
        finally:
            await service.stop()
        return compiled, results

    t0 = time.perf_counter()
    compiled, results = asyncio.run(drive())
    print(f"[C] service warmup compiled {compiled} programs; "
          f"{len(results)} queries in {time.perf_counter() - t0}s", flush=True)
    warm_violations = 0
    for (ds, q), res in zip(work, results):
        tag = f"C:{ds.name}:{q.statistic}"
        check(res.ok, f"{tag}: outcome {res.outcome} ({res.reason})")
        report_phases(tag, res.report.phases, kernel)
        warm_violations += res.report.cold
        check_same(tag, res.report, direct.run(ds, q),
                   "served and direct results")
    check(warm_violations == 0, f"C: {warm_violations} warm violations")


def four_chips(devices, kernel: str, sizes=FULL) -> None:
    """Phase A's wide query on a flat 1x4 mesh (stealing on) vs one chip."""
    check(len(devices) == 4, f"--four-chips needs 4 devices, got {len(devices)}")
    problem, scale_items, min_sup = sizes["wide"]
    ds = packed_dataset(problem, scale_items, sizes["item_tile"])
    query = ClosedFrequentQuery(min_sup=min_sup)
    reports = {}
    for n in (4, 1):
        reports[n] = make_session(devices[:n], kernel).run(ds, query)
        report_phases(f"4chips:{ds.name}:P={n}", reports[n].phases, kernel)
    check_same("4chips", reports[4], reports[1], "4-chip and 1-chip ResultSets")
    stats = reports[4].phases[-1].stats
    popped = stats["popped"].tolist()
    steals = int(stats["steals_got"].sum())
    print(f"[4chips] per_device_popped={popped} steals={steals}", flush=True)
    check(len(popped) == 4 and min(popped) > 0,
          f"4chips: a device popped no work: {popped}")
    check(steals > 0, "4chips: no steals")


def run_one_chip(devices, kernel: str, sizes=FULL) -> None:
    t0 = time.perf_counter()
    phase_wide(devices, kernel, *sizes["wide"], sizes["item_tile"])
    phase_tiled(devices, kernel, *sizes["tiled"], sizes["item_tile"])
    print(f"[A] passed in {time.perf_counter() - t0}s", flush=True)
    t0 = time.perf_counter()
    _, session = phase_lamp(devices, kernel, *sizes["lamp"])
    print(f"[B] passed in {time.perf_counter() - t0}s", flush=True)
    t0 = time.perf_counter()
    phase_service(devices, kernel, *sizes["lamp"], sizes["service_seeds"],
                  session)
    print(f"[C] passed in {time.perf_counter() - t0}s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run phase A's hapmap_dom_20 query on 4 chips vs 1")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[setup] {len(devices)} x {devices[0].device_kind}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    if args.four_chips:
        four_chips(devices[:4], "pallas")
    else:
        run_one_chip(devices[:1], "pallas")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": need}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
