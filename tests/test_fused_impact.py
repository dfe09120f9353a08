"""Fused analog IMPACT kernel: parity vs the einsum oracle across shard
layouts, plus the golden digital==analog end-to-end equivalence (Fig. 4).

The sweep inputs live in the PHYSICAL current regime (HCS reads ~5 uA,
LCS ~3 nA, CSA threshold 4.1 uA): column currents sit decades away from
the decision boundary, so CSA bits and argmax must be EXACTLY equal
between implementations; raw scores are float sums whose association
order differs, so they get an allclose with tight rtol.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import CoTMConfig, predict, train_epochs
from repro.core.cotm import clause_outputs, include_mask
from repro.data.synthetic import prototype
from repro.impact import IMPACTConfig, RuntimeSpec, build_system
from repro.impact.pipeline import IMPACTSystem
from repro.impact.yflash import I_CSA_THRESHOLD, read_current
from repro.kernels import backends, ops, ref

# (B, K, n, M, R, tr, C, tc, S, sr) — mix of single-tile, R>1/S>1 shard
# splits, ragged (non-multiple-of-block) shapes, unequal clause-axis
# paddings between the clause tile (C*tc) and class tile (S*sr), and the
# text CoTM's shape (R=5, C=3, S=3) on 128x128 tiles, where the kernel
# ANDs five shards' CSA bits across its row-shard grid axis.  The fused
# kernel reads a grid of whole-block tiles in place: column block n is
# block n % (tc // block_n) of tile n // (tc // block_n); the last three
# shapes give tiles two blocks each, with the class grid wider and
# narrower than C*tc, and 384-wide tiles, which are laid end to end.
SHARD_SHAPES = [
    (4, 100, 50, 10, 1, 128, 1, 64, 1, 64),
    (37, 300, 77, 3, 2, 150, 3, 30, 5, 16),       # R>1, S>1, ragged
    (8, 520, 500, 10, 3, 200, 2, 256, 1, 2048),   # class pad >> clause pad
    (1, 1568, 500, 10, 1, 2048, 1, 512, 1, 2048), # paper MNIST layout
    (16, 64, 33, 4, 2, 32, 3, 11, 4, 9),          # tiny ragged everything
    (24, 600, 300, 2, 5, 128, 3, 128, 3, 128),    # R=5 shard grid axis
    (16, 1000, 900, 3, 2, 512, 2, 512, 1, 2048),  # 2 blocks/tile, R, C > 1
    (8, 700, 900, 3, 3, 256, 2, 512, 3, 320),     # 2 blocks/tile, S*sr<C*tc
    (8, 200, 700, 3, 1, 256, 2, 384, 1, 704),     # 384-wide: end to end
]


def _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=0, density=0.05):
    """Synthetic programmed system in the physical current regime."""
    rng = np.random.default_rng(seed)
    lit = jnp.asarray(rng.random((B, K)) < 0.5)
    include = rng.random((R * tr, C * tc)) < density
    include[K:, :] = False                   # literal padding rows
    include[:, n:] = False                   # clause padding columns
    g = np.where(include, 2.5e-6 * (1 + 0.05 * rng.standard_normal(include.shape)),
                 0.9e-9 * (1 + 0.05 * rng.standard_normal(include.shape)))
    clause_g = jnp.asarray(g.reshape(R, tr, C, tc).transpose(0, 2, 1, 3),
                           jnp.float32)
    nonempty = jnp.asarray(include[:, :C * tc].any(axis=0))
    wg = rng.uniform(1e-9, 2.5e-6, (S, sr, M))
    wg[:, :, :] *= (np.arange(S * sr).reshape(S, sr, 1) < n)  # pad rows dead
    class_g = jnp.asarray(wg, jnp.float32)
    system = IMPACTSystem(
        clause_g=clause_g, nonempty=nonempty, class_g=class_g,
        clause_i=read_current(clause_g), class_i=read_current(class_g),
        n_literals=K, n_clauses=n, n_classes=M, cfg=IMPACTConfig(),
        encode_stats=dict(program_energy_j=0.0, erase_energy_j=0.0))
    return lit, system


@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES)
def test_fused_impact_matches_oracle(B, K, n, M, R, tr, C, tc, S, sr):
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr)
    want = ref.fused_impact_ref(lit, sys_.clause_i, sys_.nonempty,
                                sys_.class_i, thresh=I_CSA_THRESHOLD)
    got = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))


@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES)
def test_clause_bits_parity(B, K, n, M, R, tr, C, tc, S, sr):
    """Staged pallas clause stage == einsum oracle, bit-exact."""
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=1)
    f_p, i_p = sys_.clause_bits(lit, impl="pallas")
    f_x, i_x = sys_.clause_bits(lit, impl="xla")
    np.testing.assert_array_equal(np.asarray(f_p), np.asarray(f_x))
    # f32 chunked accumulation over up to R*tr rows reassociates the sum:
    # worst-case relative drift ~n_rows * eps_f32 (~2e-4 at 2048 rows).
    np.testing.assert_allclose(np.asarray(i_p), np.asarray(i_x), rtol=1e-3)


@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES[:3])
def test_class_scores_parity(B, K, n, M, R, tr, C, tc, S, sr):
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=2)
    fired, _ = sys_.clause_bits(lit, impl="xla")
    s_p, i_p = sys_.class_scores(fired, impl="pallas")
    s_x, i_x = sys_.class_scores(fired, impl="xla")
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_x), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(i_p), np.asarray(i_x), rtol=1e-6)


@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES)
def test_system_predict_parity(B, K, n, M, R, tr, C, tc, S, sr):
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=3)
    np.testing.assert_array_equal(
        np.asarray(sys_.compile(RuntimeSpec(backend="pallas"))
                   .predict(lit).predictions),
        np.asarray(sys_.compile(RuntimeSpec(backend="xla"))
                   .predict(lit).predictions))


@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES)
def test_fused_metered_matches_staged_and_oracle(B, K, n, M, R, tr, C, tc,
                                                 S, sr):
    """The tentpole parity contract: the in-kernel fused meters == the
    staged per-shard meters == the einsum oracle, across the shard-layout
    sweep.  Argmax is exact; currents are f32 sums whose association
    order differs across the three lowerings (the fused kernel chunks
    columns, the staged path chunks shards), so they get a tight rtol.
    """
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=6)
    args = (lit, sys_.clause_i, sys_.nonempty, sys_.class_i)
    want = ref.fused_impact_metered_ref(*args, thresh=I_CSA_THRESHOLD)
    fused = ops.fused_impact(*args, thresh=I_CSA_THRESHOLD, meter=True)
    # the staged meters: per-shard currents the pre-tentpole metered path
    # materialized, summed per lane (now the oracle the kernel is pinned
    # against)
    bk = backends.get_backend("pallas")
    fired, i_col = bk.impact_clause_bits(lit, sys_.clause_i, sys_.nonempty,
                                         thresh=I_CSA_THRESHOLD)
    s_scores, i_cls = bk.impact_class_scores(fired, sys_.class_i)
    staged = (s_scores, i_col.sum(axis=(1, 2, 3)), i_cls.sum(axis=(1, 2)))

    for got in (fused, staged):
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(got[0], -1)),
            np.asarray(jnp.argmax(want[0], -1)))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-6)
        # clause meter reassociates up to R*tr*C*tc f32 terms
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=1e-3)
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fused[1]), np.asarray(staged[1]),
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fused[2]), np.asarray(staged[2]),
                               rtol=1e-5)


@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr", SHARD_SHAPES)
def test_packed_backend_argmax_parity_with_int8(B, K, n, M, R, tr, C, tc,
                                                S, sr):
    """The compressed-datapath acceptance sweep: ``packing="2bit"``
    through the Pallas packed kernel agrees on argmax with the int8
    fused kernel AND the einsum oracle across every shard layout — the
    quantized clause operand preserves all CSA decisions."""
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=41)
    preds = {}
    for backend, packing in (("pallas", "none"),
                             ("pallas", "2bit"),
                             ("xla", "none")):
        sess = sys_.compile(RuntimeSpec(backend=backend, packing=packing,
                                        metering="off"))
        preds[backend, packing] = np.asarray(sess.predict(lit).predictions)
    np.testing.assert_array_equal(preds["pallas", "2bit"],
                                  preds["pallas", "none"])
    np.testing.assert_array_equal(preds["pallas", "2bit"],
                                  preds["xla", "none"])


def test_packed_session_fused_metering_matches_staged():
    """Packed sessions bill the QUANTIZED currents: the in-kernel packed
    meters must agree with the staged path (which dequantizes the same
    operand) lane for lane."""
    lit, sys_ = _make_system(16, 300, 77, 3, 2, 150, 3, 30, 5, 16, seed=43)
    buf = np.ones((16, 300), np.int8)
    buf[:11] = np.asarray(lit[:11], np.int8)
    valid = np.zeros((16,), bool)
    valid[:11] = True
    sessions = {
        mode: sys_.compile(RuntimeSpec(backend="pallas",
                                       packing="2bit", metering=mode,
                                       capacity=16))
        for mode in ("fused", "staged")}
    res = {mode: s.infer_step(buf, valid) for mode, s in sessions.items()}
    np.testing.assert_array_equal(np.asarray(res["fused"].predictions),
                                  np.asarray(res["staged"].predictions))
    np.testing.assert_allclose(np.asarray(res["fused"].e_clause_lanes),
                               np.asarray(res["staged"].e_clause_lanes),
                               rtol=1e-4, atol=0.0)
    np.testing.assert_allclose(np.asarray(res["fused"].e_class_lanes),
                               np.asarray(res["staged"].e_class_lanes),
                               rtol=1e-4, atol=0.0)
    np.testing.assert_array_equal(
        np.asarray(res["fused"].e_clause_lanes)[11:], 0.0)


def test_metered_backend_scores_identical_to_unmetered():
    """The metered kernel is the SAME datapath with meters riding
    along: its scores are bit-identical to the unmetered kernel's on the
    same backend."""
    lit, sys_ = _make_system(16, 100, 50, 10, 2, 64, 1, 64, 2, 32, seed=8)
    args = (lit, sys_.clause_i, sys_.nonempty, sys_.class_i)
    scores, _, _ = ops.fused_impact(*args, thresh=I_CSA_THRESHOLD,
                                    impl="pallas", meter=True)
    np.testing.assert_array_equal(
        np.asarray(scores),
        np.asarray(ops.fused_impact(*args, thresh=I_CSA_THRESHOLD,
                                    impl="pallas")))


def test_served_predict_reads_the_grid_the_trainer_wrote():
    """The kernel reads the programmed grid where it lies, so no copy of
    it can go stale: after ``OnlineTrainer.update`` re-programs cells,
    the session compiled before the update serves what the oracle reads
    off the mutated grid."""
    from repro.train import OnlineTrainer
    cfg = CoTMConfig(n_literals=64, n_clauses=40, n_classes=4,
                     n_states=64, threshold=16, specificity=4.0)
    x, y = prototype(320, n_classes=4, n_features=32, flip=0.05, seed=3)
    lits = jnp.asarray(np.concatenate([x, 1 - x], -1).astype(bool))
    labels = jnp.asarray(y)
    params = train_epochs(cfg.init(jax.random.key(0)), lits[:128],
                          labels[:128], jax.random.key(1), cfg, epochs=1,
                          batch_size=64)
    system = build_system(params, cfg, jax.random.key(2),
                          IMPACTConfig(variability=False, finetune=False))
    session = system.compile(RuntimeSpec(backend="pallas",
                                         batch_sizes=(64,)))
    probe = lits[256:]
    before = np.asarray(session.predict(probe).scores)
    grid = np.asarray(system.clause_i)
    trainer = OnlineTrainer(session, params, cfg, key=jax.random.key(3),
                            variability=False)
    for step in range(2):
        trainer.update(lits[128 + 64 * step:192 + 64 * step],
                       labels[128 + 64 * step:192 + 64 * step])
    assert not np.array_equal(np.asarray(system.clause_i), grid)
    got = session.predict(probe)
    want = ref.fused_impact_ref(probe, system.clause_i,
                                system._nonempty_eff(), system.class_i,
                                thresh=I_CSA_THRESHOLD)
    np.testing.assert_allclose(np.asarray(got.scores), np.asarray(want),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got.predictions),
                                  np.asarray(jnp.argmax(got.scores, -1)))
    assert not np.array_equal(np.asarray(got.scores), before)


def test_all_empty_clause_columns():
    """A tile with NO programmed clause must fire nothing and score zero
    (every column current is pure LCS leakage, masked by nonempty)."""
    B, K, n, M = 8, 96, 40, 5
    lit, sys_ = _make_system(B, K, n, M, 2, 64, 1, 64, 1, 64,
                             seed=4, density=0.0)
    assert not bool(sys_.nonempty.any())
    for impl in ("pallas", "xla"):
        fired, _ = sys_.clause_bits(lit, impl=impl)
        assert not bool(fired.any()), impl
        scores = (ops.fused_impact(lit, sys_.clause_i, sys_.nonempty,
                                   sys_.class_i, thresh=I_CSA_THRESHOLD)
                  if impl == "pallas" else
                  ref.fused_impact_ref(lit, sys_.clause_i, sys_.nonempty,
                                       sys_.class_i,
                                       thresh=I_CSA_THRESHOLD))
        np.testing.assert_array_equal(np.asarray(scores),
                                      np.zeros((B, M), np.float32))


@settings(max_examples=15, deadline=None)
@given(B=st.integers(1, 24), K=st.integers(1, 200), n=st.integers(1, 90),
       M=st.integers(1, 12), R=st.integers(1, 3), S=st.integers(1, 3),
       density=st.floats(0.0, 0.4), seed=st.integers(0, 2 ** 16))
def test_fused_impact_property(B, K, n, M, R, S, density, seed):
    """Property sweep: random shard factorizations stay oracle-exact."""
    tr = -(-K // R)
    C = 1 + seed % 3
    tc = -(-n // C)
    sr = -(-n // S)
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr,
                             seed=seed, density=density)
    want = ref.fused_impact_ref(lit, sys_.clause_i, sys_.nonempty,
                                sys_.class_i, thresh=I_CSA_THRESHOLD)
    got = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))


# --- golden end-to-end: digital CoTM == analog IMPACT (paper Fig. 4) -------

@pytest.fixture(scope="module")
def golden_trained():
    cfg = CoTMConfig(n_literals=128, n_clauses=64, n_classes=4,
                     n_states=64, threshold=16, specificity=4.0)
    x, y = prototype(768, n_classes=4, n_features=64, flip=0.05)
    lits = jnp.asarray(np.concatenate([x, 1 - x], -1).astype(bool))
    params = train_epochs(cfg.init(jax.random.key(0)), lits,
                          jnp.asarray(y), jax.random.key(1), cfg,
                          epochs=8, batch_size=64)
    return cfg, params, lits


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_golden_analog_matches_digital(golden_trained, backend):
    """Ideal devices (variability=False) + fine-tuned weight mapping must
    reproduce the digital CoTM decisions exactly — clause bits AND
    predictions (the Fig. 4 crossbar/logic equivalence)."""
    cfg, params, lits = golden_trained
    system = build_system(params, cfg, jax.random.key(2),
                          IMPACTConfig(variability=False, finetune=True))
    dig_pred = np.asarray(predict(params, lits, cfg))
    inc = include_mask(params.ta_state, cfg.n_states)
    dig_clauses = np.asarray(clause_outputs(lits, inc))

    session = system.compile(RuntimeSpec(backend=backend))
    ana_pred = np.asarray(session.predict(lits).predictions)
    fired, _ = system.clause_bits(lits, impl=backend)
    np.testing.assert_array_equal(
        np.asarray(fired)[:, :cfg.n_clauses], dig_clauses)
    np.testing.assert_array_equal(ana_pred, dig_pred)


def test_infer_with_report_consistent_across_backends(golden_trained):
    """Energy metering (staged oracle mode): both backends must report
    the same physics (same currents => same joules) and the same preds."""
    cfg, params, lits = golden_trained
    system = build_system(params, cfg, jax.random.key(2),
                          IMPACTConfig(variability=False, finetune=True))
    res_p = system.compile(RuntimeSpec(backend="pallas")) \
        .infer_with_report(lits[:64])
    res_x = system.compile(RuntimeSpec(backend="xla")) \
        .infer_with_report(lits[:64])
    rep_p, rep_x = res_p.report, res_x.report
    np.testing.assert_array_equal(np.asarray(res_p.predictions),
                                  np.asarray(res_x.predictions))
    assert rep_p.read_energy_j > 0
    np.testing.assert_allclose(rep_p.read_energy_j, rep_x.read_energy_j,
                               rtol=1e-5)
    np.testing.assert_allclose(rep_p.clause_energy_j, rep_x.clause_energy_j,
                               rtol=1e-5)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_fused_metering_report_matches_staged(golden_trained, backend):
    """metering='fused' on a TRAINED system: the single-pass in-kernel
    report carries the same joules / preds / accounting as the staged
    oracle session (the Table 4 anchors ride on this equality)."""
    cfg, params, lits = golden_trained
    system = build_system(params, cfg, jax.random.key(2),
                          IMPACTConfig(variability=False, finetune=True))
    staged = system.compile(RuntimeSpec(backend=backend,
                                        metering="staged")) \
        .infer_with_report(lits[:64])
    fused = system.compile(RuntimeSpec(backend=backend,
                                       metering="fused")) \
        .infer_with_report(lits[:64])
    np.testing.assert_array_equal(np.asarray(fused.predictions),
                                  np.asarray(staged.predictions))
    rs, rf = staged.report, fused.report
    assert rf.read_energy_j > 0
    np.testing.assert_allclose(rf.clause_energy_j, rs.clause_energy_j,
                               rtol=1e-4)
    np.testing.assert_allclose(rf.class_energy_j, rs.class_energy_j,
                               rtol=1e-4)
    assert rf.datapoints == rs.datapoints
    assert rf.latency_s == rs.latency_s
    assert rf.ops_crosspoint == rs.ops_crosspoint
