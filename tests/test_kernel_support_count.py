"""Support-count dispatch point vs oracles: parity across impls, tilings,
ragged shapes (DESIGN.md §8).

Everything here is *exact* integer math (popcount sums), so every kernel
variant, block size, and item tiling must be bit-identical — any mismatch
is a real bug, never a tolerance question.

Property tests run under hypothesis when the dev dep is installed
(requirements-dev.txt); without it the same properties run over a
deterministic pseudo-random shape sample, so the parity suite never
silently skips.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # property tests fall back to deterministic sweeps
    HAVE_HYPOTHESIS = False

from repro.core.bitmap import BitmapLayout, item_tiling, pack_db, supports_np
from repro.kernels.support_count import autotune
from repro.kernels.support_count.ops import (
    VALID_IMPLS,
    resolve_impl,
    support_counts,
    support_counts_tiled,
)

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed (dev dep)"
)


def rand_words(rng, shape):
    return rng.integers(0, 2**32, size=shape, dtype=np.uint32)


def _sample_shapes(n, dims, seed):
    """Deterministic pseudo-random shape tuples within per-dim (lo, hi)."""
    rng = np.random.default_rng(seed)
    return [
        tuple(int(rng.integers(lo, hi + 1)) for lo, hi in dims)
        for _ in range(n)
    ]


# ------------------------------------------------------------------ dispatch
def test_resolve_impl():
    assert resolve_impl("auto", backend="tpu") == "pallas"
    assert resolve_impl("auto", backend="gpu") == "pallas_gpu"
    assert resolve_impl("auto", backend="cpu") == "ref"
    for impl in VALID_IMPLS:
        assert resolve_impl(impl, backend="tpu") == impl
    with pytest.raises(ValueError, match="unknown kernel impl"):
        resolve_impl("cuda")


# ------------------------------------------------------------- shape parity
@pytest.mark.parametrize("b", [1, 3, 8, 17])
@pytest.mark.parametrize("m", [1, 5, 512, 700])
@pytest.mark.parametrize("w", [1, 7, 32, 40])
def test_shape_sweep(b, m, w):
    """Interpreted Pallas kernel == numpy oracle at ragged shapes (every dim
    both below and astride its block/floor sizes)."""
    rng = np.random.default_rng(b * 1000 + m * 10 + w)
    occ = rand_words(rng, (b, w))
    db = rand_words(rng, (m, w))
    got = np.asarray(support_counts(occ, db, impl="pallas_interpret"))
    want = supports_np(occ, db)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("blocks", [(8, 128, 8), (8, 512, 32), (16, 256, 16)])
def test_block_shape_sweep(blocks):
    """Explicit block triples (overriding the autotuner) stay bit-exact."""
    rng = np.random.default_rng(0)
    occ = rand_words(rng, (24, 50))
    db = rand_words(rng, (300, 50))
    got = np.asarray(
        support_counts(occ, db, impl="pallas_interpret", blocks=blocks)
    )
    np.testing.assert_array_equal(got, supports_np(occ, db))


def _check_packed_real_db(n, m, b, seed):
    """End-to-end: packed boolean DB + real occurrence bitmaps (all-zero
    tail bits in the last packed word exercise the padding invariance)."""
    rng = np.random.default_rng(seed)
    db = rng.random((n, m)) < 0.4
    bits = pack_db(db)  # [M, W]
    occ_rows = bits[rng.integers(0, m, size=b)]  # item columns as occurrences
    got = np.asarray(support_counts(occ_rows, bits, impl="pallas_interpret"))
    want = supports_np(occ_rows, bits)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize(
    "n,m,b,seed", _sample_shapes(8, [(1, 130), (1, 40), (1, 9), (0, 2**31 - 1)], 1)
)
def test_vs_packed_real_db(n, m, b, seed):
    _check_packed_real_db(n, m, b, seed)


if HAVE_HYPOTHESIS:

    @needs_hypothesis
    @given(
        n=st.integers(1, 130),
        m=st.integers(1, 40),
        b=st.integers(1, 9),
        seed=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=25, deadline=None)
    def test_vs_packed_real_db_hyp(n, m, b, seed):
        _check_packed_real_db(n, m, b, seed)


def test_ref_impl_path():
    rng = np.random.default_rng(5)
    occ = rand_words(rng, (4, 10))
    db = rand_words(rng, (33, 10))
    got = np.asarray(support_counts(occ, db, impl="ref"))
    np.testing.assert_array_equal(got, supports_np(occ, db))


# ----------------------------------------------------------- tiling parity
def _check_tiled_vs_untiled(b, m, w, m_tile, seed, impl):
    """Tiled sweep == untiled contraction for arbitrary (m, m_tile): m below
    one tile, m a multiple, and m astride a tile boundary all occur."""
    rng = np.random.default_rng(seed)
    occ = rand_words(rng, (b, w))
    db = rand_words(rng, (m, w))
    want = supports_np(occ, db)
    got = np.asarray(support_counts(occ, db, impl=impl, m_tile=m_tile))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize(
    "b,m,w,seed", _sample_shapes(10, [(1, 10), (1, 300), (1, 12), (0, 10**6)], 2)
)
@pytest.mark.parametrize("m_tile", [1, 64, 100, 128])
def test_tiled_vs_untiled_ref(b, m, w, m_tile, seed):
    _check_tiled_vs_untiled(b, m, w, m_tile, seed, "ref")


@pytest.mark.parametrize(
    "b,m,w,seed", _sample_shapes(5, [(1, 6), (1, 200), (1, 10), (0, 10**6)], 3)
)
def test_tiled_interpret_vs_ref(b, m, w, seed):
    """pallas_interpret through the tiled path == ref, ragged shapes."""
    _check_tiled_vs_untiled(b, m, w, 64, seed, "pallas_interpret")


if HAVE_HYPOTHESIS:

    @needs_hypothesis
    @given(
        b=st.integers(1, 10),
        m=st.integers(1, 300),
        w=st.integers(1, 12),
        m_tile=st.sampled_from([1, 8, 64, 100, 128]),
        seed=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=40, deadline=None)
    def test_tiled_vs_untiled_ref_hyp(b, m, w, m_tile, seed):
        _check_tiled_vs_untiled(b, m, w, m_tile, seed, "ref")

    @needs_hypothesis
    @given(
        b=st.integers(1, 6),
        m=st.integers(1, 200),
        w=st.integers(1, 10),
        seed=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=15, deadline=None)
    def test_tiled_interpret_vs_ref_hyp(b, m, w, seed):
        _check_tiled_vs_untiled(b, m, w, 64, seed, "pallas_interpret")


def test_tiled_entry_direct():
    """support_counts_tiled (the engine's traced entry) over a BitmapLayout:
    padded tail items report zero support."""
    rng = np.random.default_rng(7)
    m, w = 150, 4
    db = rand_words(rng, (m, w))
    layout = BitmapLayout.from_db_bits(db, m_tile=64)  # m_pad = 192
    occ = rand_words(rng, (5, w))
    got = np.asarray(support_counts_tiled(occ, layout.tiles, impl="ref"))
    assert got.shape == (5, layout.m_pad)
    np.testing.assert_array_equal(got[:, :m], supports_np(occ, db))
    assert (got[:, m:] == 0).all()


def test_all_zero_tail_words():
    """Columns whose trailing words are all zero (transactions << capacity)
    count exactly; the kernel's w-padding adds nothing."""
    rng = np.random.default_rng(11)
    occ = rand_words(rng, (6, 9))
    db = rand_words(rng, (70, 9))
    occ[:, 5:] = 0
    db[:, 5:] = 0
    for impl in ("ref", "pallas_interpret"):
        got = np.asarray(support_counts(occ, db, impl=impl))
        np.testing.assert_array_equal(got, supports_np(occ, db))


# ---------------------------------------------------------------- autotune
def test_choose_blocks_divides_bucket():
    for b, m, w in [(16, 4096, 12), (697, 11914, 22), (3, 5, 1), (64, 250112, 12)]:
        bp, mp, wp = autotune.bucket_dims(b, m, w)
        for impl in ("pallas", "pallas_interpret", "pallas_gpu"):
            bb, bm, bw = autotune.choose_blocks(b, m, w, impl)
            assert bp % bb == 0 and mp % bm == 0 and wp % bw == 0
            assert autotune.vmem_bytes(bb, bm, bw) <= autotune.VMEM_BUDGET
    assert autotune.choose_blocks(16, 4096, 12, "ref") == (0, 0, 0)


def test_choose_blocks_is_bucket_stable():
    """Every shape in one power-of-two bucket gets the same blocks — the
    program cache key never varies within a bucket."""
    picks = {
        autotune.choose_blocks(b, m, w)
        for b in (9, 12, 16) for m in (1100, 2048) for w in (5, 8)
    }
    assert len(picks) == 1


def test_seed_table_wins(tmp_path):
    b, m, w = 16, 1024, 8
    bucket = list(autotune.bucket_dims(b, m, w))
    path = tmp_path / "seed.json"
    autotune.save_seed_table(
        str(path),
        [{"impl": "pallas", "bucket": bucket, "blocks": [8, 128, 8],
          "time_us": 1.0}],
    )
    try:
        autotune.load_seed_table(str(path))
        assert autotune.choose_blocks(b, m, w, "pallas") == (8, 128, 8)
    finally:
        autotune.clear_seed_table()
    # cleared: back to the analytic pick (whatever it is, divides the bucket)
    bb, bm, bw = autotune.choose_blocks(b, m, w, "pallas")
    assert (bb, bm, bw) != (0, 0, 0)


@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
@pytest.mark.parametrize("w", [1, 8, 12, 22, 100, 129, 400, 1000])
def test_candidate_blocks_meet_tpu_lowering_rule(impl, w):
    """Every offered triple is one the TPU lowering accepts: bb a multiple
    of 8, bm of 128, and bw the whole padded word axis or a multiple of 128
    (the occ block (bb, bw) puts bw on lanes)."""
    for b in (1, 16, 100, 512):
        for m in (5, 512, 4096, 16384, 262144):
            wp = autotune.bucket_dims(b, m, w)[2]
            cands = autotune.candidate_blocks(b, m, w, impl)
            assert cands
            for bb, bm, bw in cands:
                assert bb % 8 == 0 and bm % 128 == 0, (b, m, w, (bb, bm, bw))
                assert bw == wp or bw % 128 == 0, (b, m, w, (bb, bm, bw))
                assert autotune.tpu_blocks_ok((bb, bm, bw), wp)
            assert autotune.choose_blocks(b, m, w, impl) in cands


def test_tpu_blocks_ok_refuses_narrow_partial_word_blocks():
    assert autotune.tpu_blocks_ok((16, 2048, 32), 32)
    assert autotune.tpu_blocks_ok((16, 512, 128), 512)
    assert not autotune.tpu_blocks_ok((16, 2048, 16), 32)   # bw < 128, not whole
    assert not autotune.tpu_blocks_ok((16, 64, 32), 32)     # bm below a lane row
    assert not autotune.tpu_blocks_ok((4, 2048, 32), 32)    # bb below a sublane tile


@pytest.mark.parametrize("content", [
    None, "not json", '{"rows": [{"impl": 1}]}',
    # a TPU-kernel row whose occ block (16, 16) the lowering refuses at W=32
    '[{"impl": "pallas", "bucket": [16, 4096, 32], "blocks": [16, 2048, 16],'
    ' "time_us": 1.0}]',
])
def test_unreadable_seed_file_is_an_error(tmp_path, monkeypatch, content):
    path = tmp_path / "seed.json"
    if content is not None:
        path.write_text(content)
    monkeypatch.setenv("REPRO_SC_AUTOTUNE", str(path))
    try:
        with pytest.raises(RuntimeError, match="REPRO_SC_AUTOTUNE"):
            autotune._maybe_load_env()
    finally:
        autotune.clear_seed_table()


def test_stable_jit_across_ragged_shapes():
    """The eager wrapper pads to pow2 buckets before its inner jit: every
    shape in one bucket reuses one traced program (the old wrapper re-jitted
    per distinct (b, m, w) and re-specialized block_b per odd batch)."""
    from repro.kernels.support_count.ops import _support_counts_padded

    rng = np.random.default_rng(3)
    base = _support_counts_padded._cache_size()
    for b, m, w in [(9, 1100, 5), (12, 2048, 8), (16, 1500, 7)]:
        occ = rand_words(rng, (b, w))
        db = rand_words(rng, (m, w))
        got = np.asarray(support_counts(occ, db, impl="ref"))
        np.testing.assert_array_equal(got, supports_np(occ, db))
    assert _support_counts_padded._cache_size() - base <= 1


def test_item_tiling():
    assert item_tiling(100) == (100, 100)          # single tile, zero pad
    assert item_tiling(4096) == (4096, 4096)
    assert item_tiling(4097) == (8192, 4096)
    assert item_tiling(250_120) == (253_952, 4096)  # 62 tiles
    assert item_tiling(10, 4) == (12, 4)
