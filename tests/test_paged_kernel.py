"""Paged-attention Pallas kernel (interpret mode) vs the live-length oracle.

Contracts under test:

  * parity — the block-table-walk kernel reproduces the reference across
    ragged positions, sliding windows, logit softcap, GQA ratios, chunked
    prefill (S > 1), and null-block padding (idle rows, padded chunk tails);
  * fused scatter — the kernel's in-prologue ``write_kv`` leaves the pools
    bit-identical to the reference scatter on every non-null page,
    including pages it never visits (input/output aliasing);
  * live-block early exit — walking only ``max_live_blocks`` blocks gives
    the same answer as gathering the full table width;
  * the walk's geometry — each row walks its own live pages in compute
    blocks whose size the kernel derives from the shapes: rows of very
    different depths beside padded rows, live lengths ending mid-page and
    mid-block, chunks straddling two blocks, pages shared by two rows, and
    no write outside the fresh pages, in any layer's range of the pool;
  * unified ragged mode — the flat one-token-per-row batch of the unified
    tick (decode rows + prefill segments, walked per request through the
    ``row_map`` view) matches the flat scatter-first oracle on BOTH
    backends, including intra-chunk causality and segments straddling
    page boundaries;
  * end-to-end — ``PagedServingEngine(use_pallas=True, interpret=True)``
    stays token-identical to isolated greedy ``generate`` (the engine's
    default tick routes through the unified ragged kernel).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention import ref as paged_ref

FULL = 1 << 30


def head_major(pool):
    """(NB, BS, Hkv, ...) -> the pools' (NB, Hkv, BS, ...) layout.  Cases
    draw page contents slot by slot, so each (page, slot, head) holds the
    same value whatever the layout."""
    return jnp.swapaxes(pool, 1, 2)


def make_case(seed, *, S, filled, ns, Hkv, G, BS, MB, D=16,
              dtype=jnp.float32):
    """Random pools/tables for one tick.

    filled[b] = tokens already in row b's cache; ns[b] = fresh tokens this
    tick (0 = idle row with a null table).  Pools are random everywhere so
    stale/unallocated pages hold garbage a leaky mask would pick up.
    """
    rng = np.random.default_rng(seed)
    B = len(filled)
    H = Hkv * G
    NB = 1 + B * MB
    pos = np.full((B, S), -1, np.int32)
    tables = np.zeros((B, MB), np.int32)
    page = 1
    for b, (f, n) in enumerate(zip(filled, ns)):
        if n > 0:
            pos[b, :n] = f + np.arange(n)
            nblk = (f + n - 1) // BS + 1
            tables[b, :nblk] = np.arange(page, page + nblk)
            page += nblk
    arr = lambda *shape: jnp.asarray(
        rng.standard_normal(shape), jnp.float32).astype(dtype)
    return dict(q=arr(B, S, H, D), kn=arr(B, S, Hkv, D),
                vn=arr(B, S, Hkv, D), kp=head_major(arr(NB, BS, Hkv, D)),
                vp=head_major(arr(NB, BS, Hkv, D)),
                tables=jnp.asarray(tables),
                pos=jnp.asarray(pos), np_pos=pos,
                live=int(pos.max()) // BS + 1 if (pos >= 0).any() else 1)


def run_both(c, *, window, softcap, live=None):
    win = jnp.asarray(window, jnp.int32)
    live = c["live"] if live is None else live
    kr, vr = paged_ref.write_kv(c["kp"], c["vp"], c["kn"], c["vn"],
                                c["pos"], c["tables"])
    out_r = paged_ref.paged_attention(c["q"], kr, vr, c["tables"], c["pos"],
                                      window=win, softcap=softcap,
                                      max_live_blocks=live)
    out_k, kk, vk = paged_ops.paged_attention_update(
        c["q"], c["kn"], c["vn"], c["kp"], c["vp"], c["tables"], c["pos"],
        window=win, softcap=softcap, max_live_blocks=live,
        use_pallas=True, interpret=True)
    return out_r, (kr, vr), out_k, (kk, vk)


def assert_parity(c, out_r, pools_r, out_k, pools_k, tol=3e-5):
    valid = c["np_pos"] >= 0
    np.testing.assert_allclose(
        np.asarray(out_r, np.float32)[valid],
        np.asarray(out_k, np.float32)[valid], atol=tol, rtol=tol)
    # fused scatter: bit-identical pools on every non-null page — visited
    # pages got the same writes, unvisited pages persisted via aliasing
    # (the null page is garbage by design on both paths)
    for r, k in zip(pools_r, pools_k):
        np.testing.assert_array_equal(np.asarray(r)[1:], np.asarray(k)[1:])


@pytest.mark.parametrize("Hkv,G", [(1, 4), (2, 2), (2, 3), (4, 1)])
def test_decode_parity_ragged_gqa(Hkv, G):
    """S=1 decode: ragged live lengths, an idle (null-table) row, all GQA
    group ratios including MHA (G=1) and MQA-style (Hkv=1)."""
    c = make_case(10 + G, S=1, filled=[0, 7, 21, 0], ns=[1, 1, 1, 0],
                  Hkv=Hkv, G=G, BS=4, MB=8)
    assert_parity(c, *run_both(c, window=FULL, softcap=0.0))


@pytest.mark.parametrize("window,softcap", [(6, 0.0), (FULL, 30.0),
                                            (5, 20.0), (1, 0.0)])
def test_decode_parity_window_softcap(window, softcap):
    """Sliding windows (incl. degenerate window=1) and logit softcap."""
    c = make_case(3, S=1, filled=[13, 3, 29], ns=[1, 1, 1],
                  Hkv=2, G=2, BS=4, MB=10)
    assert_parity(c, *run_both(c, window=window, softcap=softcap))


@pytest.mark.parametrize("filled,ns", [
    ([0, 2, 0], [4, 3, 0]),      # fresh prefill + ragged tail + idle row
    ([5, 0, 9], [4, 4, 2]),      # chunks starting mid-page
    ([3, 14, 7], [1, 2, 4]),     # mixed chunk widths, page-crossing
])
def test_chunked_prefill_parity(filled, ns):
    """S>1 prefill chunks: -1-padded tails, page-boundary crossings, and
    causal masking *within* the fresh chunk."""
    c = make_case(int(sum(filled)), S=4, filled=filled, ns=ns,
                  Hkv=2, G=2, BS=4, MB=10)
    assert_parity(c, *run_both(c, window=FULL, softcap=0.0))
    c2 = make_case(int(sum(ns)), S=4, filled=filled, ns=ns,
                   Hkv=2, G=2, BS=4, MB=10)
    assert_parity(c2, *run_both(c2, window=5, softcap=0.0))


def test_live_block_early_exit_matches_full_walk():
    """Bounding the walk at the live maximum == gathering the full table:
    entries past a row's live length are invisible either way."""
    c = make_case(42, S=1, filled=[2, 9, 0], ns=[1, 1, 1],
                  Hkv=2, G=2, BS=4, MB=16)
    out_r, pr, out_k, pk = run_both(c, window=FULL, softcap=0.0)
    assert c["live"] == 3 < 16
    out_full, _, out_kf, _ = run_both(c, window=FULL, softcap=0.0, live=16)
    valid = c["np_pos"] >= 0
    np.testing.assert_allclose(np.asarray(out_r)[valid],
                               np.asarray(out_full)[valid],
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(out_k)[valid],
                               np.asarray(out_kf)[valid],
                               atol=3e-5, rtol=3e-5)
    assert_parity(c, out_r, pr, out_k, pk)


def test_null_block_padding_is_harmless():
    """Idle rows (null tables) and padded chunk tails: finite output,
    nothing scattered outside the null page, live rows unaffected."""
    c = make_case(7, S=2, filled=[0, 4], ns=[0, 2], Hkv=2, G=2,
                  BS=4, MB=6)
    out_r, pools_r, out_k, pools_k = run_both(c, window=FULL, softcap=0.0)
    assert np.isfinite(np.asarray(out_k)).all()
    assert_parity(c, out_r, pools_r, out_k, pools_k)
    # the idle row's table is all-null; no real page may have been touched
    # by it — pages beyond the live row's two blocks kept their old bits
    touched = np.unique(np.asarray(c["tables"])[1, :2])
    kp_old, kp_new = np.asarray(c["kp"]), np.asarray(pools_k[0])
    untouched = np.setdiff1d(np.arange(1, kp_old.shape[0]), touched)
    np.testing.assert_array_equal(kp_old[untouched], kp_new[untouched])


def test_bf16_pools_parity():
    """bf16 pools/queries: fused scatter casts once, walk stays close."""
    c = make_case(11, S=1, filled=[6, 17], ns=[1, 1], Hkv=2, G=2,
                  BS=4, MB=8, dtype=jnp.bfloat16)
    assert_parity(c, *run_both(c, window=FULL, softcap=0.0), tol=2e-2)


def test_readonly_op_matches_reference():
    """The read-only op (no fused scatter) over already-written pools."""
    c = make_case(5, S=1, filled=[9, 25, 2], ns=[1, 1, 1], Hkv=2, G=1,
                  BS=4, MB=10)
    win = jnp.asarray(FULL, jnp.int32)
    kr, vr = paged_ref.write_kv(c["kp"], c["vp"], c["kn"], c["vn"],
                                c["pos"], c["tables"])
    out_r = paged_ref.paged_attention(c["q"], kr, vr, c["tables"], c["pos"],
                                      window=win, softcap=0.0,
                                      max_live_blocks=c["live"])
    out_k = paged_ops.paged_attention(c["q"], kr, vr, c["tables"], c["pos"],
                                      window=win, softcap=0.0,
                                      max_live_blocks=c["live"],
                                      use_pallas=True, interpret=True)
    valid = c["np_pos"] >= 0
    np.testing.assert_allclose(np.asarray(out_r)[valid],
                               np.asarray(out_k)[valid],
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# int8 KV pools: fused quantized scatter + per-page dequant (DESIGN.md §13)
# ---------------------------------------------------------------------------

def quantize_case(c, seed=0):
    """Convert a fp ``make_case``/``make_ragged_case`` dict to int8 pools:
    random int8 garbage everywhere (stale pages must stay garbage) plus
    fp32 per-row scale pools.  Fresh k/v rows stay fp — quantization is
    the op's job (fused into the scatter on both backends)."""
    rng = np.random.default_rng(seed)
    out = dict(c)
    for name in ("kp", "vp"):
        NB, Hkv, BS, D = c[name].shape
        out[name] = head_major(jnp.asarray(
            rng.integers(-127, 128, size=(NB, BS, Hkv, D)), jnp.int8))
        out[name[0] + "s"] = head_major(jnp.asarray(
            np.abs(rng.standard_normal((NB, BS, Hkv))), jnp.float32))
    return out


def run_both_int8(c, *, window, softcap, live=None):
    """int8 flavor of ``run_both``: 5-tuple returns, scale pools ride
    along and must come back bit-identical across backends."""
    win = jnp.asarray(window, jnp.int32)
    live = c["live"] if live is None else live
    kr, vr, ksr, vsr = paged_ref.write_kv(
        c["kp"], c["vp"], c["kn"], c["vn"], c["pos"], c["tables"],
        c["ks"], c["vs"])
    out_r = paged_ref.paged_attention(c["q"], kr, vr, c["tables"], c["pos"],
                                      window=win, softcap=softcap,
                                      max_live_blocks=live,
                                      k_scale=ksr, v_scale=vsr)
    out_k, kk, vk, ksk, vsk = paged_ops.paged_attention_update(
        c["q"], c["kn"], c["vn"], c["kp"], c["vp"], c["tables"], c["pos"],
        window=win, softcap=softcap, max_live_blocks=live,
        use_pallas=True, interpret=True, k_scale=c["ks"], v_scale=c["vs"])
    return out_r, (kr, vr, ksr, vsr), out_k, (kk, vk, ksk, vsk)


def test_int8_quantize_roundtrip_error_bound():
    """The shared recipe: dequant(quantize(x)) is within half a
    quantization step (amax/254) per row, zero rows survive exactly."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, 3, 16)), jnp.float32)
    x = x.at[2].set(0.0)
    q, s = paged_ref.quantize_rows(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    back = paged_ref.dequantize(q, s)
    err = np.abs(np.asarray(back - x))
    bound = np.asarray(s)[..., None] / 2 + 1e-7
    assert (err <= bound).all()
    np.testing.assert_array_equal(np.asarray(back[2]), 0.0)


@pytest.mark.parametrize("Hkv,G", [(1, 4), (2, 2), (4, 1)])
def test_int8_decode_parity_ragged_gqa(Hkv, G):
    """S=1 decode over int8 pools: kernel == reference at the fp
    tolerance (both dequantize the SAME int8 bits), pools + scale pools
    bit-identical on every non-null page."""
    c = quantize_case(make_case(10 + G, S=1, filled=[0, 7, 21, 0],
                                ns=[1, 1, 1, 0], Hkv=Hkv, G=G, BS=4, MB=8),
                      seed=G)
    assert_parity(c, *run_both_int8(c, window=FULL, softcap=0.0))


@pytest.mark.parametrize("window,softcap", [(6, 0.0), (FULL, 30.0)])
def test_int8_window_softcap_parity(window, softcap):
    c = quantize_case(make_case(3, S=1, filled=[13, 3, 29], ns=[1, 1, 1],
                                Hkv=2, G=2, BS=4, MB=10), seed=1)
    assert_parity(c, *run_both_int8(c, window=window, softcap=softcap))


def test_int8_chunked_prefill_parity():
    """S>1 chunks: every fresh row quantizes into its page slot with its
    own scale; page-crossing chunks land rows on both pages."""
    c = quantize_case(make_case(9, S=4, filled=[5, 0, 9], ns=[4, 4, 2],
                                Hkv=2, G=2, BS=4, MB=10), seed=2)
    assert_parity(c, *run_both_int8(c, window=FULL, softcap=0.0))


@pytest.mark.parametrize("backend", ["pallas", "reference"])
def test_int8_unified_ragged_parity(backend):
    """The unified ragged tick over int8 pools — decode rows, prefill
    segments, and a draft chain — matches the flat scatter-first oracle
    on both backends; scale pools come back bit-identical."""
    c = quantize_case(
        make_ragged_case(50, segments=[(9, 1), (0, 4), (25, 5), (13, 1)],
                         Hkv=2, G=2, BS=4, MB=9, pad=2), seed=3)
    win = jnp.asarray(FULL, jnp.int32)
    out_r, kr, vr, ksr, vsr = paged_ref.unified_attention_update(
        c["q"], c["kn"], c["vn"], c["kp"], c["vp"], c["tables"], c["pos"],
        window=win, softcap=0.0, max_live_blocks=c["live"],
        k_scale=c["ks"], v_scale=c["vs"])
    out_k, kk, vk, ksk, vsk = paged_ops.paged_attention_unified(
        c["q"], c["kn"], c["vn"], c["kp"], c["vp"], c["tables_req"],
        c["pos"], c["row_map"], window=win, softcap=0.0,
        max_live_blocks=c["live"], max_seg_len=c["max_seg"],
        use_pallas=backend == "pallas", interpret=True,
        k_scale=c["ks"], v_scale=c["vs"])
    assert_parity(c, out_r, (kr, vr, ksr, vsr), out_k, (kk, vk, ksk, vsk))


def test_int8_copy_page_carries_scales():
    """COW on quantized pools: ``copy_page`` moves the int8 page AND its
    scale page (rank-generic pool handling), other pages persist."""
    rng = np.random.default_rng(21)
    L, NB, Hkv, BS, D = 2, 5, 2, 4, 8
    pool = jnp.asarray(rng.integers(-127, 128, (L, NB, Hkv, BS, D)),
                       jnp.int8)
    spool = jnp.asarray(np.abs(rng.standard_normal((L, NB, Hkv, BS))),
                        jnp.float32)
    for p in (pool, spool):
        got = paged_ops.copy_page(p, jnp.int32(1), jnp.int32(3),
                                  use_pallas=True, interpret=True)
        want = paged_ref.copy_page(p, 1, 3)
        assert jnp.array_equal(want, got)
        keep = [i for i in range(NB) if i != 3]
        assert jnp.array_equal(got[:, keep], p[:, keep])


# ---------------------------------------------------------------------------
# the walk: each row's own live pages, in multi-page compute blocks
# ---------------------------------------------------------------------------

def walk_plan(c):
    """(pages per compute block, query rows per tile) the kernel derives
    for case ``c``."""
    B, S, H, D = c["q"].shape
    _, Hkv, BS, _ = c["kp"].shape
    _, P, QB, _, _ = paged_kernel._plan(
        SG=S * (H // Hkv), Hkv=Hkv, BS=BS, D=D, nb=c["live"],
        q_dtype=c["q"].dtype, pool_dtype=c["kp"].dtype, NF=1, quant=False)
    return P, QB


@pytest.mark.parametrize("window", [FULL, 100])
@pytest.mark.parametrize("filled,ns", [
    ([1100, 0, 37, 0, 300], [1, 1, 1, 0, 1]),  # 1 page .. 2 blocks, padded
    ([1057, 0, 16, 250], [1, 0, 1, 1]),        # ends mid-page, mid-block
])
def test_walk_rows_of_mixed_depths(filled, ns, window):
    """Rows of very different depths and padded rows in one call: each
    row stops at its own depth, the deepest walks two compute blocks (a
    window of 100 starts its walk in the last pages of the first, which
    its query sees only in part)."""
    c = make_case(60 + len(filled), S=1, filled=filled, ns=ns, Hkv=2, G=2,
                  BS=16, MB=72)
    assert walk_plan(c)[0] < c["live"]
    out_r, pools_r, out_k, pools_k = run_both(c, window=window, softcap=0.0)
    assert np.isfinite(np.asarray(out_k)).all()
    assert_parity(c, out_r, pools_r, out_k, pools_k)


@pytest.mark.parametrize("window", [FULL, 50])
def test_walk_chunk_spans_two_blocks(window):
    """A chunk at 256 query rows whose fresh rows 1010..1041 straddle the
    boundary of two 1024-key blocks and two page boundaries: every page
    is written before either block attends."""
    c = make_case(71, S=64, filled=[1010, 100, 0], ns=[32, 64, 0], Hkv=1,
                  G=4, BS=16, MB=70)
    P, QB = walk_plan(c)
    assert (P, QB) == (64, 256) and (1010 // 16) // P != (1041 // 16) // P
    assert_parity(c, *run_both(c, window=window, softcap=0.0))


def test_walk_shared_prefix_page():
    """Two rows read the same full pages, as the prefix cache shares
    them, while each writes only its own tail page."""
    c = make_case(81, S=2, filled=[40, 45], ns=[2, 2], Hkv=2, G=2, BS=16,
                  MB=8)
    tables = np.array(c["tables"])
    tables[1, :2] = tables[0, :2]            # positions 0..31, both rows
    c["tables"] = jnp.asarray(tables)
    out_r, pools_r, out_k, pools_k = run_both(c, window=FULL, softcap=0.0)
    assert_parity(c, out_r, pools_r, out_k, pools_k)
    for before, after in zip((c["kp"], c["vp"]), pools_k):
        np.testing.assert_array_equal(np.asarray(after)[tables[0, :2]],
                                      np.asarray(before)[tables[0, :2]])


def test_walk_writes_only_fresh_pages():
    """On a flat pool of three layers, walked pages that hold no fresh
    row and every page of the other layers' ranges are byte-identical
    after the call; the fresh pages are the reference's."""
    c = make_case(91, S=4, filled=[37, 0, 70, 0], ns=[4, 0, 3, 1], Hkv=2,
                  G=2, BS=16, MB=8)
    NB = c["kp"].shape[0]
    rng = np.random.default_rng(92)

    def three_layers(pool):
        other = lambda: jnp.asarray(  # noqa: E731
            rng.standard_normal(pool.shape), pool.dtype)
        return jnp.concatenate([other(), pool, other()])
    c["kp"], c["vp"] = three_layers(c["kp"]), three_layers(c["vp"])
    c["tables"] = c["tables"] + NB               # the middle layer's pages
    out_r, (kr, vr), out_k, pools_k = run_both(c, window=FULL, softcap=0.0)
    valid = c["np_pos"] >= 0
    np.testing.assert_allclose(np.asarray(out_r)[valid],
                               np.asarray(out_k)[valid], atol=3e-5,
                               rtol=3e-5)
    pos, tab = c["np_pos"], np.asarray(c["tables"])
    fresh = {int(tab[b, p // 16]) for b, p in zip(*np.nonzero(pos >= 0))
             for p in [pos[b, p]]}
    keep = [p for p in range(3 * NB) if p not in fresh and p != NB]
    assert len(fresh) == 3 and len(keep) == 3 * NB - 4
    for before, want, after in zip((c["kp"], c["vp"]), (kr, vr), pools_k):
        np.testing.assert_array_equal(np.asarray(after)[keep],
                                      np.asarray(before)[keep])
        np.testing.assert_array_equal(np.asarray(after)[sorted(fresh)],
                                      np.asarray(want)[sorted(fresh)])


@pytest.mark.parametrize("window,softcap", [(100, 30.0), (FULL, 20.0)])
def test_int8_walk_large_query_block(window, softcap):
    """int8 pages under a window and softcap at 256 query rows, walked in
    256-row query tiles (one 8-row tile at a decode row) over two
    1024-key blocks, the window starting inside the first.  The
    stored scales are amax/127-sized, as ``quantize_rows`` makes them for
    unit-scale rows: with ``quantize_case``'s unit scales the dequantized
    keys reach 127 and f32 rounding alone (kernel and reference alike)
    moves the outputs by more than the tolerance at this depth."""
    c = quantize_case(make_case(95, S=64, filled=[1000, 10, 0],
                                ns=[64, 20, 0], Hkv=1, G=4, BS=16, MB=70),
                      seed=5)
    c["ks"], c["vs"] = c["ks"] / 127.0, c["vs"] / 127.0
    small = make_case(95, S=1, filled=[1063], ns=[1], Hkv=1, G=4, BS=16,
                      MB=70)
    assert walk_plan(c) == (64, 256) and walk_plan(small) == (64, 8)
    assert c["live"] > 64
    assert_parity(c, *run_both_int8(c, window=window, softcap=softcap))


# ---------------------------------------------------------------------------
# unified ragged mode: flat token batch walked per request via row_map
# ---------------------------------------------------------------------------

def make_ragged_case(seed, *, segments, Hkv, G, BS, MB, D=16, pad=1,
                     dtype=jnp.float32):
    """Flat unified-tick pack: ``segments`` = [(filled, n_fresh), ...] —
    one request per segment contributing ``n_fresh`` consecutive tokens
    starting at position ``filled`` (n_fresh=1 models a decode).  Rows of
    a segment are contiguous and share the request's block table; ``pad``
    appends dead rows (pos=-1, null table — at least one, as the engine
    guarantees: the per-request ``row_map``'s dead entries point there)."""
    assert pad >= 1
    rng = np.random.default_rng(seed)
    real = sum(n for _, n in segments)
    T = real + pad
    H = Hkv * G
    max_seg = max((n for _, n in segments), default=1)
    pos = np.full((T, 1), -1, np.int32)
    tables = np.zeros((T, MB), np.int32)          # per token (oracle view)
    tables_req = np.zeros((len(segments), MB), np.int32)  # per request (op)
    row_map = np.full((len(segments), max_seg), real, np.int32)
    r, page = 0, 1
    for i, (f, n) in enumerate(segments):
        nblk = (f + n - 1) // BS + 1
        tab = np.zeros(MB, np.int32)
        tab[:nblk] = np.arange(page, page + nblk)
        page += nblk
        pos[r:r + n, 0] = f + np.arange(n)
        tables[r:r + n] = tab
        tables_req[i] = tab
        row_map[i, :n] = np.arange(r, r + n)
        r += n
    NB = page
    arr = lambda *shape: jnp.asarray(
        rng.standard_normal(shape), jnp.float32).astype(dtype)
    return dict(q=arr(T, 1, H, D), kn=arr(T, 1, Hkv, D),
                vn=arr(T, 1, Hkv, D), kp=head_major(arr(NB, BS, Hkv, D)),
                vp=head_major(arr(NB, BS, Hkv, D)),
                tables=jnp.asarray(tables),
                tables_req=jnp.asarray(tables_req),
                pos=jnp.asarray(pos), np_pos=pos,
                row_map=jnp.asarray(row_map),
                live=int(pos.max()) // BS + 1 if (pos >= 0).any() else 1,
                max_seg=max_seg)


def run_ragged(c, backend, *, window, softcap, live=None, max_seg=None):
    """One unified-op backend over the pack: the flat scatter-first oracle
    (``oracle``, O(T*live) — validation only), or the production op's
    per-request row_map walk on the ``reference`` / ``pallas`` backend
    (Pallas = the multi-query block-table-walk kernel in interpret mode)."""
    win = jnp.asarray(window, jnp.int32)
    live = c["live"] if live is None else live
    max_seg = c["max_seg"] if max_seg is None else max_seg
    if backend == "oracle":
        return paged_ref.unified_attention_update(
            c["q"], c["kn"], c["vn"], c["kp"], c["vp"], c["tables"],
            c["pos"], window=win, softcap=softcap, max_live_blocks=live)
    return paged_ops.paged_attention_unified(
        c["q"], c["kn"], c["vn"], c["kp"], c["vp"], c["tables_req"],
        c["pos"], c["row_map"], window=win, softcap=softcap,
        max_live_blocks=live, max_seg_len=max_seg,
        use_pallas=backend == "pallas", interpret=True)


def run_both_ragged(c, *, window, softcap, live=None, max_seg=None,
                    backend="pallas"):
    out_r, kr, vr = run_ragged(c, "oracle", window=window, softcap=softcap,
                               live=live, max_seg=max_seg)
    out_k, kk, vk = run_ragged(c, backend, window=window, softcap=softcap,
                               live=live, max_seg=max_seg)
    return out_r, (kr, vr), out_k, (kk, vk)


@pytest.mark.parametrize("backend", ["pallas", "reference"])
@pytest.mark.parametrize("Hkv,G", [(1, 4), (2, 2), (4, 1)])
def test_unified_ragged_parity_mixed_phases(Hkv, G, backend):
    """Decode rows and prefill segments in one flat batch: the unified
    op's per-request row_map walk matches the flat scatter-first oracle
    on both backends, so a chunk token always sees its intra-tick
    predecessors."""
    c = make_ragged_case(20 + G, segments=[(9, 1), (0, 4), (6, 3), (13, 1)],
                         Hkv=Hkv, G=G, BS=4, MB=8, pad=2)
    assert_parity(c, *run_both_ragged(c, window=FULL, softcap=0.0,
                                      backend=backend))


@pytest.mark.parametrize("backend", ["pallas", "reference"])
def test_unified_ragged_parity_draft_chains(backend):
    """Speculative verify segments (DESIGN.md §11): decode rows carrying
    a multi-token draft chain mid-sequence (filled > 0, n_fresh > 1)
    must match the oracle at EVERY chain position, not just the last —
    the engine reads logits at all of them through the verify mask, so a
    last-position-only contract would silently break accept/rollback."""
    c = make_ragged_case(42, segments=[(25, 5), (7, 3), (0, 4), (12, 1)],
                         Hkv=2, G=2, BS=4, MB=9, pad=2)
    assert_parity(c, *run_both_ragged(c, window=FULL, softcap=0.0,
                                      backend=backend))
    # a sliding window narrower than the chain still agrees everywhere
    c2 = make_ragged_case(43, segments=[(17, 4), (9, 2)], Hkv=1, G=4,
                          BS=4, MB=8)
    assert_parity(c2, *run_both_ragged(c2, window=3, softcap=0.0,
                                       backend=backend))


@pytest.mark.parametrize("backend", ["pallas", "reference"])
@pytest.mark.parametrize("window,softcap", [(5, 0.0), (FULL, 25.0),
                                            (1, 0.0)])
def test_unified_ragged_window_softcap(window, softcap, backend):
    c = make_ragged_case(31, segments=[(11, 1), (2, 4), (5, 2)],
                         Hkv=2, G=2, BS=4, MB=8)
    assert_parity(c, *run_both_ragged(c, window=window, softcap=softcap,
                                      backend=backend))


def test_unified_ragged_chunk_crosses_page_boundary():
    """A segment whose fresh tokens straddle two pages: each visited page
    must receive exactly the fresh rows that land on it."""
    c = make_ragged_case(7, segments=[(2, 4), (6, 3)], Hkv=2, G=2,
                         BS=4, MB=6)
    out_r, pr, out_k, pk = run_both_ragged(c, window=FULL, softcap=0.0)
    assert_parity(c, out_r, pr, out_k, pk)
    # over-wide static segment bound (kernel clamps) changes nothing
    out_r2, pr2, out_k2, pk2 = run_both_ragged(c, window=FULL, softcap=0.0,
                                               max_seg=8)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_k2))
    for a, b in zip(pk, pk2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unified_ragged_live_block_early_exit():
    """Bounding the ragged walk at the tick's live maximum equals the
    full-table walk (per-row early exit keeps each request clamped)."""
    c = make_ragged_case(13, segments=[(1, 1), (10, 2)], Hkv=2, G=2,
                         BS=4, MB=12)
    out_r, pr, out_k, pk = run_both_ragged(c, window=FULL, softcap=0.0)
    assert c["live"] == 3 < 12
    _, _, out_kf, _ = run_both_ragged(c, window=FULL, softcap=0.0, live=12)
    valid = c["np_pos"] >= 0
    np.testing.assert_allclose(np.asarray(out_k)[valid],
                               np.asarray(out_kf)[valid],
                               atol=3e-5, rtol=3e-5)
    assert_parity(c, out_r, pr, out_k, pk)


# ---------------------------------------------------------------------------
# end-to-end: the serving engine on the kernel path
# ---------------------------------------------------------------------------

def _generate_ref(cfg, params, prompt, gen):
    from repro.launch.serve import generate
    out = generate(cfg, params, jnp.asarray(prompt)[None], gen)
    return np.asarray(out)[0, len(prompt):].tolist()


def test_engine_pallas_interpret_token_exact():
    """PagedServingEngine(use_pallas=True, interpret=True) emits exactly
    the tokens isolated greedy generate would — ragged prompts, chunked
    prefill crossing page boundaries, slot reuse."""
    from repro.config import get_config, reduced
    from repro.models import model as M
    from repro.serving import PagedServingEngine
    cfg = reduced(get_config("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=8, prefill_chunk=3,
                             use_pallas=True, interpret=True)
    assert eng.metrics()["attention_backend"] == "pallas-interpret"
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 8, 3)]
    gens = [5, 3, 4]
    ids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    results = eng.run_to_completion()
    for rid, p, g in zip(ids, prompts, gens):
        assert results[rid] == _generate_ref(cfg, params, p, g)


def test_engine_pallas_sliding_window_token_exact():
    """Kernel path under per-layer sliding windows (local + global mix)."""
    from repro.config import get_config, reduced
    from repro.models import model as M
    from repro.serving import PagedServingEngine
    cfg = reduced(get_config("granite-3-2b"), sliding_window=6,
                  global_every=2)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=8, prefill_chunk=4,
                             use_pallas=True, interpret=True)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (9, 5)]
    ids = [eng.submit(p, 5) for p in prompts]
    results = eng.run_to_completion()
    for rid, p in zip(ids, prompts):
        assert results[rid] == _generate_ref(cfg, params, p, 5)


# ---------------------------------------------------------------------------
# copy_page: the engine's copy-on-write primitive (DESIGN.md §9)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_copy_page_parity(dtype):
    """Pallas copy_page (interpret) == reference on a stacked (L, NB, ...)
    pool: the destination page carries the source's rows in every layer
    and every other page persists bit-identically (aliasing)."""
    rng = np.random.default_rng(11)
    L, NB, Hkv, BS, D = 3, 6, 2, 4, 8
    pool = jnp.asarray(rng.standard_normal((L, NB, Hkv, BS, D)),
                       jnp.float32).astype(dtype)
    src, dst = 2, 5
    want = paged_ref.copy_page(pool, src, dst)
    got = paged_ops.copy_page(pool, jnp.int32(src), jnp.int32(dst),
                              use_pallas=True, interpret=True)
    assert jnp.array_equal(want, got)
    # the copy touched only page dst; the source page is intact
    assert jnp.array_equal(got[:, dst], pool[:, src])
    keep = [p for p in range(NB) if p != dst]
    assert jnp.array_equal(got[:, keep], pool[:, keep])


def test_copy_page_traced_ids_single_jit():
    """src/dst are traced scalars: one jit of the caller serves every
    page pair on both backends."""
    rng = np.random.default_rng(12)
    pool = jnp.asarray(rng.standard_normal((2, 5, 4, 1, 8)), jnp.float32)
    for use_pallas in (False, True):
        fn = jax.jit(lambda p, s, d: paged_ops.copy_page(
            p, s, d, use_pallas=use_pallas, interpret=True))
        for src, dst in ((1, 3), (4, 2)):
            got = fn(pool, jnp.int32(src), jnp.int32(dst))
            assert jnp.array_equal(got, paged_ref.copy_page(pool, src, dst))


def test_engine_cow_pallas_interpret_token_exact():
    """End-to-end COW through the Pallas kernel path: a fully-cached
    aligned prompt re-served must copy its tail page (never mutating the
    cached page) and still match isolated greedy generation."""
    import repro.models.model as M
    from repro.config import get_config, reduced
    from repro.launch.serve import generate
    from repro.serving import PagedServingEngine
    cfg = reduced(get_config("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab, 8).astype(np.int32)  # 2 full pages
    ref_toks = np.asarray(generate(cfg, params,
                                   jnp.asarray(prompt)[None], 4))[0, 8:]
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=6, prefill_chunk=4,
                             prefix_cache=True, use_pallas=True,
                             interpret=True)
    a = eng.submit(prompt, 4)
    assert eng.run_to_completion()[a] == ref_toks.tolist()
    b = eng.submit(prompt.copy(), 4)
    assert eng.run_to_completion()[b] == ref_toks.tolist()
    pc = eng.metrics()["prefix_cache"]
    assert pc["cow_copies"] >= 1 and pc["hit_tokens"] == 7
