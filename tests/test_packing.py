"""2-bit ternary clause packing: pack/unpack round-trips (including
non-multiple-of-4 row counts), data-driven classification of the bimodal
Y-Flash current populations, and the packed fused kernel against the
packed einsum oracle.

Parity contract (same convention as test_fused_impact): quantization
collapses per-cell currents to their class means, so packed-vs-unpacked
raw scores only agree loosely — but CSA bits and argmax are EXACT on
these systems because column currents sit decades from the decision
boundary.  Packed kernel vs packed ORACLE is a tight allclose: both
consume the identical quantized currents.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.impact.yflash import I_CSA_THRESHOLD
from repro.kernels import ops, packing, ref

from test_fused_impact import SHARD_SHAPES, _make_system


# -- pack / unpack round trip ------------------------------------------------

@pytest.mark.parametrize("K", [1, 2, 3, 4, 5, 7, 8, 127, 128, 130])
def test_pack_unpack_roundtrip(K):
    """Every row count round-trips, multiple of 4 or not."""
    rng = np.random.default_rng(K)
    codes = rng.integers(0, 3, (K, 33)).astype(np.uint8)
    packed = packing.pack_ternary(codes)
    assert packed.shape == (packing.packed_rows(K), 33)
    assert packed.dtype == jnp.uint8
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_ternary(packed, K)), codes)


@settings(max_examples=25, deadline=None)
@given(K=st.integers(1, 200), N=st.integers(1, 40),
       seed=st.integers(0, 2 ** 16))
def test_pack_unpack_roundtrip_property(K, N, seed):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 3, (K, N)).astype(np.uint8)
    got = packing.unpack_ternary(packing.pack_ternary(codes), K)
    np.testing.assert_array_equal(np.asarray(got), codes)


def test_bitfield_layout_contract():
    """Bit-field j of packed row q is original row 4q+j — the layout the
    Pallas kernel's in-register unpack assumes."""
    codes = np.asarray([[1], [2], [0], [1], [2]], np.uint8)  # K=5
    packed = np.asarray(packing.pack_ternary(codes))
    assert packed.shape == (2, 1)
    assert packed[0, 0] == (1 << 0) | (2 << 2) | (0 << 4) | (1 << 6)
    assert packed[1, 0] == 2                   # row 4, padding rows DEAD


# -- classification + quantization -------------------------------------------

def test_population_split_lands_between_regimes():
    """The geometric midpoint sits decades from both device populations,
    including far-tail HCS cells BELOW the CSA column threshold (the case
    that rules out using the CSA threshold as the split)."""
    rng = np.random.default_rng(0)
    hcs = 5e-6 * (1 + 0.05 * rng.standard_normal(200))
    hcs[0] = 4.0e-6                 # -5 sigma tail, below I_CSA_THRESHOLD
    lcs = 2.7e-9 * (1 + 0.05 * rng.standard_normal(200))
    cur = jnp.asarray(np.concatenate([hcs, lcs, [0.0]]), jnp.float32)
    split = float(packing.population_split(cur))
    assert lcs.max() < split < hcs.min()
    codes = np.asarray(packing.classify_currents(cur))
    assert (codes[:200] == packing.CODE_HCS).all()   # tail cell included
    assert (codes[200:400] == packing.CODE_LCS).all()
    assert codes[400] == packing.CODE_DEAD


def test_quant_levels_and_dequant():
    cur = jnp.asarray([0.0, 2e-9, 4e-9, 5e-6, 7e-6], jnp.float32)
    codes = packing.classify_currents(cur)
    levels = packing.quant_levels(cur, codes)
    np.testing.assert_allclose(np.asarray(levels), [3e-9, 6e-6], rtol=1e-6)
    deq = np.asarray(packing.dequant_codes(codes, levels))
    np.testing.assert_allclose(deq, [0.0, 3e-9, 3e-9, 6e-6, 6e-6],
                               rtol=1e-6)
    # single-population operand: split == the common value, all HCS
    flat = jnp.full((4,), 5e-6, jnp.float32)
    assert (np.asarray(packing.classify_currents(flat))
            == packing.CODE_HCS).all()


@pytest.mark.parametrize("tr", [32, 33, 150])          # incl. tr % 4 != 0
def test_pack_clause_operand_roundtrip(tr):
    """(R, C, tr, tc) operand packs 4:1 on the row axis and dequants back
    to the class-mean currents with codes preserved exactly."""
    lit, sys_ = _make_system(4, 100, 50, 10, 2, tr, 2, 32, 1, 64, seed=5)
    packed = packing.pack_clause_operand(sys_.clause_i)
    R, C, _, tc = sys_.clause_i.shape
    assert packed.bits.shape == (R, C, packing.packed_rows(tr), tc)
    assert packed.bits.dtype == jnp.uint8
    deq = packing.dequant_clause(packed.bits, packed.levels, tr)
    assert deq.shape == sys_.clause_i.shape
    np.testing.assert_array_equal(
        np.asarray(packing.classify_currents(deq)),
        np.asarray(packing.classify_currents(sys_.clause_i)))
    # the packed operand is ~16x smaller than the f32 currents it encodes
    assert packing.packed_nbytes(packed) * 8 \
        < sys_.clause_i.size * sys_.clause_i.dtype.itemsize


# -- packed oracle vs unpacked oracle ----------------------------------------

@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES)
def test_packed_oracle_argmax_parity(B, K, n, M, R, tr, C, tc, S, sr):
    """Quantization to class means preserves every CSA decision and the
    argmax across the shard-layout sweep."""
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=31)
    packed = packing.pack_clause_operand(sys_.clause_i)
    want = ref.fused_impact_ref(lit, sys_.clause_i, sys_.nonempty,
                                sys_.class_i, thresh=I_CSA_THRESHOLD)
    got = ref.fused_impact_packed_ref(lit, packed.bits, packed.levels,
                                      sys_.nonempty, sys_.class_i,
                                      thresh=I_CSA_THRESHOLD, tr=tr)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))


# -- packed Pallas kernel vs packed oracle -----------------------------------

@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES)
def test_packed_kernel_matches_packed_oracle(B, K, n, M, R, tr, C, tc,
                                             S, sr):
    """The in-kernel 2-bit unpack computes the same quantized physics as
    the dequant-then-einsum oracle: tight allclose + exact argmax."""
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=33)
    packed = packing.pack_clause_operand(sys_.clause_i)
    want = ref.fused_impact_packed_ref(lit, packed.bits, packed.levels,
                                       sys_.nonempty, sys_.class_i,
                                       thresh=I_CSA_THRESHOLD, tr=tr)
    got = ops.fused_impact(lit, packed, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD, tr=tr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))


@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES[:3])
def test_packed_metered_matches_packed_oracle(B, K, n, M, R, tr, C, tc,
                                              S, sr):
    """The metered packed kernel bills the QUANTIZED currents — meters
    match the packed metered oracle, scores match the unmetered kernel."""
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=35)
    packed = packing.pack_clause_operand(sys_.clause_i)
    want = ref.fused_impact_packed_metered_ref(
        lit, packed.bits, packed.levels, sys_.nonempty, sys_.class_i,
        thresh=I_CSA_THRESHOLD, tr=tr)
    got = ops.fused_impact(lit, packed, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD, tr=tr, meter=True)
    plain = ops.fused_impact(lit, packed, sys_.nonempty, sys_.class_i,
                             thresh=I_CSA_THRESHOLD, tr=tr)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got[0], -1)),
                                  np.asarray(jnp.argmax(want[0], -1)))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(plain),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-5)


def test_every_backend_serves_the_packed_operand():
    """Packing is a clause-operand type every registered backend accepts:
    the xla oracle and the pallas packed kernel agree on argmax."""
    B, K, n, M, R, tr, C, tc, S, sr = SHARD_SHAPES[1]
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=37)
    packed = packing.pack_clause_operand(sys_.clause_i)
    preds = {}
    for impl in ("xla", "pallas"):
        scores = ops.fused_impact(
            lit, packed, sys_.nonempty, sys_.class_i,
            thresh=I_CSA_THRESHOLD, tr=tr, impl=impl)
        preds[impl] = np.asarray(jnp.argmax(scores, -1))
    np.testing.assert_array_equal(preds["pallas"], preds["xla"])
