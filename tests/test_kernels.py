"""Per-kernel allclose sweeps (shapes x dtypes) against the ref.py oracles,
in Pallas interpret mode (the CPU-validation target per the assignment)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [
    # (n_q, n_c, n_docs, cap, m, ksub)
    (32, 256, 64, 16, 8, 16),
    (32, 640, 100, 24, 16, 16),
    (16, 512, 130, 32, 8, 256),   # n_q < 32; non-multiple doc count
    (4, 1024, 33, 8, 4, 256),     # MIND-like n_q=4
]


def _inputs(n_q, n_c, n_docs, cap, m, ksub, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    cs = jnp.asarray(rng.normal(size=(n_q, n_c)).astype(dtype))
    codes = jnp.asarray(rng.integers(0, n_c + 1, size=(n_docs, cap)
                                     ).astype(np.int32))
    lens = rng.integers(1, cap + 1, size=n_docs)
    mask = jnp.asarray(np.arange(cap)[None, :] < lens[:, None])
    lut = jnp.asarray(rng.normal(size=(n_q, m, ksub)).astype(dtype))
    res = jnp.asarray(rng.integers(0, ksub, size=(n_docs, cap, m)
                                   ).astype(np.uint8))
    return cs, codes, mask, lut, res


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("th", [-0.5, 0.0, 0.5, 2.0])
def test_bitpack(shape, th):
    cs, *_ = _inputs(*shape)
    np.testing.assert_array_equal(np.asarray(ops.bitpack(cs, th)),
                                  np.asarray(ref.bitpack(cs, th)))


@pytest.mark.parametrize("shape", SHAPES)
def test_bitfilter(shape):
    cs, codes, mask, _, _ = _inputs(*shape)
    bits = ref.bitpack(cs, 0.3)
    np.testing.assert_array_equal(
        np.asarray(ops.bitfilter(bits, codes, mask)),
        np.asarray(ref.bitfilter(bits, codes, mask)))


@pytest.mark.parametrize("shape", SHAPES)
def test_cinter(shape):
    cs, codes, mask, _, _ = _inputs(*shape)
    out = ops.cinter(cs.T, codes, mask)
    exp = ref.cinter(cs.T, codes, mask)
    # fp32 sum-of-maxes: kernel accumulates per-block, ref in one reduce —
    # accumulation order differs, so allow normal fp32 slack.
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-4,
                               atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("th_r", [None, 0.3])
def test_pqscore(shape, th_r):
    cs, codes, mask, lut, res = _inputs(*shape)
    out = ops.pqscore(cs.T, lut, codes, res, mask, th_r)
    exp = ref.pqscore(cs.T, lut, codes, res, mask, th_r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5,
                               atol=1e-5)


def test_bitpack_block_boundary():
    """n_c not a multiple of the block: padding must not flip bits."""
    cs, *_ = _inputs(32, 700, 8, 8, 4, 16)
    out = ops.bitpack(cs, 0.1)
    exp = ref.bitpack(cs, 0.1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_pqscore_bf16_tolerance():
    cs, codes, mask, lut, res = _inputs(32, 256, 32, 16, 8, 16)
    out = ops.pqscore(cs.T.astype(jnp.bfloat16).astype(jnp.float32), lut,
                      codes, res, mask, 0.3)
    exp = ref.pqscore(cs.T.astype(jnp.bfloat16).astype(jnp.float32), lut,
                      codes, res, mask, 0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5,
                               atol=1e-5)


def test_empty_docs_masked_out():
    """A doc with zero valid tokens must score popcount 0 / NEG maxsim."""
    cs, codes, mask, lut, res = _inputs(32, 256, 16, 8, 4, 16)
    mask = mask.at[3].set(False)
    bits = ref.bitpack(cs, 0.0)
    f = np.asarray(ops.bitfilter(bits, codes, mask))
    assert f[3] == 0


# ---------------------------------------------------------------------------
# Query-term masking: every masked kernel against its masked oracle
# ---------------------------------------------------------------------------

def _q_mask(n_q, seed=0):
    """A random mask with at least one live and one dead term."""
    rng = np.random.default_rng(seed + 101)
    qm = rng.random(n_q) < 0.6
    qm[0], qm[-1] = True, False
    return jnp.asarray(qm)


def test_bitpack_q_mask_zeroes_masked_bits():
    cs, *_ = _inputs(*SHAPES[1])
    qm = _q_mask(cs.shape[0])
    out = np.asarray(ops.bitpack(cs, 0.2, qm))
    np.testing.assert_array_equal(out, np.asarray(ref.bitpack(cs, 0.2, qm)))
    dead_bits = np.uint32(0)
    for i, live in enumerate(np.asarray(qm)):
        if not live:
            dead_bits |= np.uint32(1) << np.uint32(i)
    assert (out & dead_bits == 0).all()


def test_cinter_q_mask_matches_ref():
    cs, codes, mask, _, _ = _inputs(*SHAPES[1])
    qm = _q_mask(cs.shape[0])
    out = ops.cinter(cs.T, codes, mask, qm)
    exp = ref.cinter(cs.T, codes, mask, qm)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("th_r", [None, 0.3])
def test_pqscore_q_mask_matches_ref(th_r):
    cs, codes, mask, lut, res = _inputs(*SHAPES[0])
    qm = _q_mask(cs.shape[0])
    out = ops.pqscore(cs.T, lut, codes, res, mask, th_r, qm)
    exp = ref.pqscore(cs.T, lut, codes, res, mask, th_r, qm)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_prefilter_fused_q_mask_matches_ref():
    cs, codes, mask, _, _ = _inputs(*SHAPES[1])
    qm = _q_mask(cs.shape[0])
    n_docs = codes.shape[0]
    s, i, bits = ops.prefilter(cs, 0.2, codes, mask, _bitmap(n_docs),
                               n_docs // 3, qm)
    rs, ri = ref.prefilter(cs, 0.2, codes, mask, _bitmap(n_docs),
                           n_docs // 3, qm)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(bits),
                                  np.asarray(ref.bitpack(cs, 0.2, qm)))


def test_pqinter_fused_q_mask_matches_ref():
    cs, codes, mask, lut, res = _inputs(*SHAPES[0])
    qm = _q_mask(cs.shape[0])
    out = ops.pqinter(cs.T, lut, codes, res, mask, 0.5, 20, 7, qm)
    exp = ref.pqinter(cs.T, lut, codes, res, mask, 0.5, 20, 7, qm)
    for got, want, name in zip(out, exp, ("scores", "pos", "sel2", "sbar")):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


def test_all_terms_masked_scores_zero():
    """All-False mask: S̄ and Eq. 5/6 scores collapse to exactly 0.0 (the
    empty sum) for every doc, masked bit words are all-zero."""
    cs, codes, mask, lut, res = _inputs(*SHAPES[0])
    qm = jnp.zeros((cs.shape[0],), jnp.bool_)
    assert (np.asarray(ops.bitpack(cs, 0.2, qm)) == 0).all()
    assert (np.asarray(ops.cinter(cs.T, codes, mask, qm)) == 0.0).all()
    assert (np.asarray(ops.pqscore(cs.T, lut, codes, res, mask, 0.3,
                                   qm)) == 0.0).all()


# ---------------------------------------------------------------------------
# Fused prefilter megakernel (phases 1b-2 in one launch)
# ---------------------------------------------------------------------------

def _bitmap(n_docs, seed=0, density=0.4):
    rng = np.random.default_rng(seed + 1)
    return jnp.asarray(rng.random(n_docs) < density)


def _assert_prefilter_matches_ref(cs, codes, mask, bitmap, n_filter, th=0.2):
    s, i, bits = ops.prefilter(cs, th, codes, mask, bitmap, n_filter)
    rs, ri = ref.prefilter(cs, th, codes, mask, bitmap, n_filter)
    # selection parity is BIT-EXACT, including lax.top_k tie order
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
    # the byproduct bit table equals the standalone bitpack
    np.testing.assert_array_equal(np.asarray(bits),
                                  np.asarray(ref.bitpack(cs, th)))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("th", [-0.5, 0.5, 2.0])
def test_prefilter_fused(shape, th):
    cs, codes, mask, _, _ = _inputs(*shape)
    n_docs = codes.shape[0]
    _assert_prefilter_matches_ref(cs, codes, mask, _bitmap(n_docs),
                                  max(1, n_docs // 3), th)


@pytest.mark.parametrize("shape", [SHAPES[0], SHAPES[3]])
def test_prefilter_fused_full_and_tiny_nfilter(shape):
    """n_filter == n_docs (everything survives, order must still match) and
    n_filter == 1 (running merge degenerates to an argmax)."""
    cs, codes, mask, _, _ = _inputs(*shape, seed=3)
    n_docs = codes.shape[0]
    bm = _bitmap(n_docs, seed=3)
    _assert_prefilter_matches_ref(cs, codes, mask, bm, n_docs)
    _assert_prefilter_matches_ref(cs, codes, mask, bm, 1)


def test_prefilter_fused_block_boundary():
    """Doc counts straddling the block size: padded rows must never be
    selected ahead of real docs (even real docs with f == -1)."""
    for n_docs in (255, 257):
        cs, codes, mask, _, _ = _inputs(32, 256, n_docs, 16, 8, 16, seed=7)
        _assert_prefilter_matches_ref(cs, codes, mask,
                                      _bitmap(n_docs, seed=7), n_docs // 2)


def test_prefilter_fused_all_docs_masked():
    """bitmap all-False: ref top_k ranks a flat -1 array, i.e. doc ids in
    index order with score -1 — the fused tie-break must reproduce that."""
    cs, codes, mask, _, _ = _inputs(32, 256, 64, 16, 8, 16)
    s, i, _ = ops.prefilter(cs, 0.2, codes, mask, jnp.zeros(64, bool), 16)
    np.testing.assert_array_equal(np.asarray(i), np.arange(16))
    assert (np.asarray(s) == -1).all()


def test_prefilter_fused_zero_token_docs():
    """Docs whose every token is padding score popcount 0, not -1 (they are
    still candidates if the bitmap says so)."""
    cs, codes, mask, _, _ = _inputs(32, 256, 64, 16, 8, 16)
    mask = mask.at[5].set(False)
    bm = jnp.ones(64, bool)
    _assert_prefilter_matches_ref(cs, codes, mask, bm, 64)
    s, i, _ = ops.prefilter(cs, 0.2, codes, mask, bm, 64)
    assert np.asarray(s)[np.asarray(i) == 5] == 0


def test_prefilter_fused_bf16_cs():
    """bf16 centroid scores: threshold comparison happens in the CS dtype on
    both sides, so parity stays bit-exact."""
    cs, codes, mask, _, _ = _inputs(32, 640, 100, 24, 16, 16)
    _assert_prefilter_matches_ref(cs.astype(jnp.bfloat16), codes, mask,
                                  _bitmap(100), 40, th=0.1)


# ---------------------------------------------------------------------------
# Fused phase-3/4 megakernel (centroid interaction + selection + PQ late
# interaction + final top-k in one launch)
# ---------------------------------------------------------------------------

def _tie_heavy(n_q, n_c, n_docs, cap, m, ksub, seed=0, levels=2):
    """Inputs whose scores collide constantly: CS and LUT quantized to
    ``levels`` distinct values, so both the phase-3 S̄ selection and the
    final top-k are decided by tie-breaking almost everywhere."""
    cs, codes, mask, lut, res = _inputs(n_q, n_c, n_docs, cap, m, ksub, seed)
    cs = jnp.asarray(np.round(np.asarray(cs) * levels) / levels)
    lut = jnp.asarray(np.round(np.asarray(lut) * levels) / levels)
    return cs, codes, mask, lut, res


def _assert_pqinter_matches_ref(cs, codes, mask, lut, res, th_r, n_docs, k):
    out = ops.pqinter(cs.T, lut, codes, res, mask, th_r, n_docs, k)
    exp = ref.pqinter(cs.T, lut, codes, res, mask, th_r, n_docs, k)
    for got, want, name in zip(out, exp, ("scores", "pos", "sel2", "sbar")):
        # selection AND score parity are BIT-EXACT, incl. lax.top_k tie order
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


# SHAPES[1] is exercised by test_pqinter_fused_all_terms_filtered below —
# keeping it out of the sweep saves two compiles of the unrolled megakernel.
@pytest.mark.parametrize("shape", [SHAPES[0], SHAPES[2], SHAPES[3]])
@pytest.mark.parametrize("th_r", [None, 0.5])
def test_pqinter_fused(shape, th_r):
    """Eq. 5 (th_r=None) and Eq. 6 (th_r=0.5) across the shape sweep."""
    cs, codes, mask, lut, res = _inputs(*shape)
    n_docs = max(1, codes.shape[0] // 3)
    _assert_pqinter_matches_ref(cs, codes, mask, lut, res, th_r, n_docs,
                                max(1, n_docs // 4))


@pytest.mark.parametrize("shape,th_r", [(SHAPES[0], 0.5), (SHAPES[3], None)])
def test_pqinter_fused_tie_heavy(shape, th_r):
    """Quantized score distributions: ranking is almost entirely tie-breaks,
    which must match lax.top_k's lowest-index order at BOTH selections."""
    cs, codes, mask, lut, res = _tie_heavy(*shape)
    n_docs = max(1, codes.shape[0] // 2)
    _assert_pqinter_matches_ref(cs, codes, mask, lut, res, th_r, n_docs,
                                max(1, n_docs // 3))


def test_pqinter_fused_selection_boundaries():
    """n_docs == n_filter (phase 3 selects everything — order must still
    match) with k == n_docs, and k == 1 (the final merge degenerates to an
    argmax)."""
    cs, codes, mask, lut, res = _inputs(*SHAPES[0], seed=5)
    n = codes.shape[0]
    _assert_pqinter_matches_ref(cs, codes, mask, lut, res, 0.5, n, n)
    _assert_pqinter_matches_ref(cs, codes, mask, lut, res, 0.5, n, 1)


def test_pqinter_fused_empty_survivors():
    """Every survivor slot is padding (all tokens masked): scores collapse
    to the n_q * NEG floor and the top-k must fall back to index order."""
    cs, codes, mask, lut, res = _inputs(32, 256, 64, 16, 8, 16)
    empty = jnp.zeros_like(mask)
    _assert_pqinter_matches_ref(cs, codes, empty, lut, res, 0.5, 32, 10)
    scores, pos, _, _ = ops.pqinter(cs.T, lut, codes, res, empty, 0.5, 32, 10)
    np.testing.assert_array_equal(np.asarray(pos), np.arange(10))


def test_pqinter_fused_all_terms_filtered():
    """th_r above every centroid score: every J̄_i is empty, so Eq. 6 must
    fall back to Eq. 5 for every term — and still match the ref bitwise."""
    cs, codes, mask, lut, res = _inputs(32, 640, 100, 24, 16, 16)
    _assert_pqinter_matches_ref(cs, codes, mask, lut, res, 1e9, 40, 10)
    s_eq6, p_eq6, _, _ = ops.pqinter(cs.T, lut, codes, res, mask, 1e9, 40, 10)
    s_eq5, p_eq5, _, _ = ops.pqinter(cs.T, lut, codes, res, mask, None, 40, 10)
    np.testing.assert_array_equal(np.asarray(p_eq6), np.asarray(p_eq5))
    np.testing.assert_array_equal(np.asarray(s_eq6), np.asarray(s_eq5))


def test_pqinter_fused_bf16_cs():
    """bf16 centroid scores: S̄ rides bf16 exactly like the reference (the
    f32 cast in the merge is lossless and order-preserving), and the Eq. 6
    threshold comparison happens in the CS dtype on both sides — parity
    stays bit-exact, selections and score bits included."""
    cs, codes, mask, lut, res = _inputs(32, 640, 100, 24, 16, 16)
    cs16 = cs.astype(jnp.bfloat16)
    _assert_pqinter_matches_ref(cs16, codes, mask, lut, res, 0.5, 40, 10)
    _assert_pqinter_matches_ref(cs16, codes, mask, lut, res, None, 40, 10)


def test_pqinter_fused_block_boundaries():
    """Survivor counts straddling the pass-1 block and n_docs straddling the
    pass-2 block (explicit small blocks so both loops run >1 iteration with
    a ragged tail): padded rows / dead buffer lanes must never be selected."""
    from repro.kernels.pqinter import pqinter

    for n_docs, nd in ((95, 33), (97, 31)):
        cs, codes, mask, lut, res = _inputs(32, 256, n_docs, 16, 8, 16,
                                            seed=7)
        out = pqinter(cs.T, lut, codes, res, mask, 0.3, nd, 9,
                      block_d1=32, block_d2=16, interpret=True)
        exp = ref.pqinter(cs.T, lut, codes, res, mask, 0.3, nd, 9)
        for got, want, name in zip(out, exp, ("scores", "pos", "sel2",
                                              "sbar")):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=name)
