"""Sharded fused analog crossbar: the shard_map lowering of
``sharding/crossbar.py`` against the single-device Pallas kernel and the
einsum oracle — fully sharded (R and S both on the model axis) AND the
asymmetric R-only / S-only plans where the non-dividing operand is
replicated.

Parity contract (same convention as test_fused_impact): CSA bits and
argmax predictions are EXACTLY equal across lowerings on ideal devices —
column currents sit decades from the CSA decision boundary — while raw
class-current scores are float sums whose association order changes under
``psum``, so they get an allclose with tight rtol.

The multi-device sweeps need >= 2 devices and are exercised in CI with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the multi-device
leg, every PR); on a single-device host they skip, and a subprocess
smoke test keeps one real 8-device parity + billing run in the tier-1
lane (with ``JAX_PLATFORMS=cpu`` pinned — see the comment at the call).
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.impact import RuntimeSpec, Topology
from repro.impact.yflash import I_CSA_THRESHOLD
from repro.kernels import ops, ref
from repro.launch.mesh import make_crossbar_mesh
from repro.serve import IMPACTEngine
from repro.sharding import crossbar

from test_fused_impact import _make_system

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _mesh_or_skip(n_model: int):
    if jax.device_count() % n_model:
        pytest.skip(f"{jax.device_count()} devices not divisible by "
                    f"n_model={n_model}")
    return make_crossbar_mesh(n_model=n_model)


# (B, K, n, M, R, tr, C, tc, S, sr, n_model) — R > 1 AND S > 1 grids per
# the acceptance criteria, ragged shapes, shards-per-device > 1, and a
# full-width model axis (R == S == n_model == 8).
SHARD_SHAPES = [
    (16, 300, 120, 7, 4, 80, 3, 40, 4, 30, 2),     # 2 shards/device
    (16, 300, 120, 7, 4, 80, 3, 40, 4, 30, 4),     # 1 shard/device
    (8, 520, 500, 10, 4, 130, 2, 256, 2, 250, 2),  # class pad >> clause pad
    (4, 64, 33, 4, 8, 8, 3, 11, 8, 5, 8),          # tiny ragged, full axis
]

# Asymmetric layouts: exactly one of R / S divides the model axis, so the
# plan shards that operand and replicates the other (the lifted PR-3
# restriction).
ASYM_SHAPES = [
    # R-only: R=4 % 2 == 0, S=3 % 2 != 0 -> plan (True, False)
    (8, 300, 120, 7, 4, 80, 3, 40, 3, 40, 2, (True, False)),
    # S-only: R=3 % 2 != 0, S=4 % 2 == 0 -> plan (False, True)
    (8, 300, 126, 7, 3, 100, 3, 42, 4, 32, 2, (False, True)),
    # R-only on a wider axis, shards-per-device > 1
    (16, 512, 96, 5, 8, 64, 2, 48, 3, 32, 4, (True, False)),
]


class FakeMesh:
    def __init__(self, **axes):
        self.shape = dict(axes)


def test_shard_plan_and_gate():
    """The placement resolver that routes between the shard_map lowering
    (fully sharded or asymmetric) and the single-device fallback."""
    m = FakeMesh(data=2, model=4)
    assert crossbar.shard_plan(m, 4, 8) == (True, True)
    assert crossbar.shard_plan(m, 3, 4) == (False, True)   # S-only
    assert crossbar.shard_plan(m, 4, 6) == (True, False)   # R-only
    assert crossbar.shard_plan(m, 3, 6) is None            # neither
    assert crossbar.shard_plan(None, 4, 4) is None
    assert crossbar.shard_plan(FakeMesh(data=8), 4, 4) is None  # no model
    assert crossbar.shard_plan(FakeMesh(data=4, model=1), 4, 4) is None
    assert crossbar.shard_plan(m, 3, 6, mode="none") is None
    # an explicitly demanded placement must never silently no-op: a mesh
    # without a usable model axis raises instead of falling back
    for degenerate in (FakeMesh(data=8), FakeMesh(data=4, model=1)):
        with pytest.raises(ValueError, match="model axis"):
            crossbar.shard_plan(degenerate, 4, 4, mode="both")
    # explicit modes validate at resolution time
    assert crossbar.shard_plan(m, 4, 6, mode="r") == (True, False)
    with pytest.raises(ValueError, match="divide the model axis"):
        crossbar.shard_plan(m, 4, 6, mode="both")
    with pytest.raises(ValueError, match="divide the model axis"):
        crossbar.shard_plan(m, 3, 4, mode="r")
    with pytest.raises(ValueError, match="shard mode"):
        crossbar.shard_plan(m, 4, 4, mode="diagonal")
    assert crossbar.shardable(m, 4, 6)          # any plan counts
    assert not crossbar.shardable(m, 3, 6)
    assert crossbar.data_axes(FakeMesh(pod=2, data=2, model=2)) == \
        ("pod", "data")
    assert crossbar.data_axes(FakeMesh(model=2)) == ()


def test_model_axis_of_one_falls_back_single_device():
    """A degenerate (1, 1) mesh must route through the single-device
    kernel bit-for-bit (this covers the fallback on tier-1's one CPU)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    lit, sys_ = _make_system(8, 150, 60, 5, 2, 80, 2, 32, 2, 32, seed=5)
    want = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                            thresh=I_CSA_THRESHOLD)
    got = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@multi_device
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr,n_model", SHARD_SHAPES)
def test_shmap_matches_single_device_and_oracle(B, K, n, M, R, tr, C, tc,
                                                S, sr, n_model, impl):
    """The acceptance sweep: shard_map fused inference over a >= 2-device
    model axis vs the single-device Pallas kernel vs the einsum oracle."""
    mesh = _mesh_or_skip(n_model)
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=7)
    want = ref.fused_impact_ref(lit, sys_.clause_i, sys_.nonempty,
                                sys_.class_i, thresh=I_CSA_THRESHOLD)
    single = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty,
                              sys_.class_i, thresh=I_CSA_THRESHOLD)
    got = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD, impl=impl, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(single),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(single, -1)))


@multi_device
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("B,K,n,M,R,tr,C,tc,S,sr,n_model,plan", ASYM_SHAPES)
def test_asymmetric_plan_matches_single_device_and_oracle(
        B, K, n, M, R, tr, C, tc, S, sr, n_model, plan, impl):
    """R-only / S-only plans (the other operand replicated) stay
    score-allclose and argmax-exact vs the oracle and the single-device
    kernel — the lifted both-must-divide restriction."""
    mesh = _mesh_or_skip(n_model)
    assert crossbar.shard_plan(mesh, R, S) == plan
    lit, sys_ = _make_system(B, K, n, M, R, tr, C, tc, S, sr, seed=21)
    want = ref.fused_impact_ref(lit, sys_.clause_i, sys_.nonempty,
                                sys_.class_i, thresh=I_CSA_THRESHOLD)
    single = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty,
                              sys_.class_i, thresh=I_CSA_THRESHOLD)
    got = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD, impl=impl, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(single),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))


@multi_device
@pytest.mark.parametrize("shard,plan", [("r", (True, False)),
                                        ("s", (False, True))])
def test_topology_forces_asymmetric_plan(shard, plan):
    """RuntimeSpec(topology=Topology(shard='r'|'s')) pins the placement
    at compile time even when both operands could shard; predictions
    stay parity with the unsharded session."""
    mesh = _mesh_or_skip(2)
    lit, sys_ = _make_system(8, 300, 120, 7, 4, 80, 3, 40, 4, 30, seed=23)
    forced = sys_.compile(RuntimeSpec(
        backend="xla", topology=Topology(mesh=mesh, shard=shard)))
    assert forced.plan == plan
    base = sys_.compile(RuntimeSpec(backend="xla"))
    np.testing.assert_array_equal(
        np.asarray(forced.predict(lit).predictions),
        np.asarray(base.predict(lit).predictions))


@multi_device
def test_indivisible_batch_replicates():
    """B that doesn't divide the data axis still shards the model axis
    (the batch replicates instead of failing)."""
    mesh = _mesh_or_skip(2)            # data axis = device_count // 2 > 1
    B = mesh.shape["data"] * 2 + 1     # never divisible by the data axis
    lit, sys_ = _make_system(B, 300, 120, 7, 4, 80, 3, 40, 4, 30, seed=9)
    want = ref.fused_impact_ref(lit, sys_.clause_i, sys_.nonempty,
                                sys_.class_i, thresh=I_CSA_THRESHOLD)
    got = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD, impl="xla", mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@multi_device
def test_no_plan_falls_back_exactly():
    """R=3, S=3 over a model axis of 2: no plan exists, so the wrapper
    must take the single-device kernel path bit-for-bit (same code path
    => exact)."""
    mesh = _mesh_or_skip(2)
    lit, sys_ = _make_system(8, 150, 60, 5, 3, 64, 2, 32, 3, 20, seed=11)
    assert crossbar.shard_plan(mesh, 3, 3) is None
    want = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                            thresh=I_CSA_THRESHOLD)
    got = ops.fused_impact(lit, sys_.clause_i, sys_.nonempty, sys_.class_i,
                           thresh=I_CSA_THRESHOLD, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@multi_device
@pytest.mark.parametrize("metering", ["staged", "fused"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("R,tr,S,sr", [
    (4, 80, 4, 30),      # fully sharded plan
    (4, 80, 3, 40),      # asymmetric R-only plan
])
def test_metered_infer_step_parity_under_sharding(backend, metering,
                                                  R, tr, S, sr):
    """Sharded metered sweep == single-device metered path: same preds
    (sentinel -1 on free lanes), same per-lane energy bills, free lanes
    billed exactly zero — for the fully sharded AND asymmetric plans (a
    replicated stage's currents must not be psummed into m-fold bills),
    and for BOTH metering modes (a sharded topology lowers them to the
    same psummed datapath; single-device 'fused' runs the in-kernel
    meters — the four (plan, mode) corners of the acceptance sweep)."""
    mesh = _mesh_or_skip(2)
    B, K = 8, 300
    lit, sys_ = _make_system(B, K, 120, 7, R, tr, 3, 40, S, sr, seed=13)
    buf = np.ones((B, K), np.int8)
    buf[:5] = np.asarray(lit[:5])
    valid = np.zeros((B,), bool)
    valid[:5] = True
    s_one = sys_.compile(RuntimeSpec(backend=backend, metering=metering,
                                     capacity=B))
    s_mesh = sys_.compile(RuntimeSpec(
        backend=backend, metering=metering, capacity=B,
        topology=Topology(mesh=mesh)))
    assert s_mesh.plan == (True, S % 2 == 0)
    r1 = s_one.infer_step(buf, valid)
    rm = s_mesh.infer_step(buf, valid)
    p_1, p_m = np.asarray(r1.predictions), np.asarray(rm.predictions)
    np.testing.assert_array_equal(p_1, p_m)
    assert (p_m[5:] == -1).all(), p_m
    np.testing.assert_allclose(np.asarray(rm.e_clause_lanes),
                               np.asarray(r1.e_clause_lanes), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(rm.e_class_lanes),
                               np.asarray(r1.e_class_lanes), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(rm.e_clause_lanes)[5:], 0.0)
    np.testing.assert_array_equal(np.asarray(rm.e_class_lanes)[5:], 0.0)


@multi_device
@pytest.mark.parametrize("shard", ["both", "r", "s", "none"])
def test_fused_metering_bills_identically_across_shard_plans(shard):
    """RuntimeSpec(metering='fused') under all four forced shard plans
    (both / R-only / S-only / none): per-lane meters agree with the
    single-device staged oracle — the ISSUE acceptance sweep.  'none'
    forces the single-device in-kernel meters even on a meshed system;
    the sharded plans psum the meters with replicated operands billed
    exactly once."""
    mesh = _mesh_or_skip(2)
    B, K = 8, 300
    lit, sys_ = _make_system(B, K, 120, 7, 4, 80, 3, 40, 4, 30, seed=15)
    buf = np.ones((B, K), np.int8)
    buf[:6] = np.asarray(lit[:6])
    valid = np.zeros((B,), bool)
    valid[:6] = True
    oracle = sys_.compile(RuntimeSpec(backend="xla", metering="staged",
                                      capacity=B)).infer_step(buf, valid)
    sess = sys_.compile(RuntimeSpec(
        backend="xla", metering="fused", capacity=B,
        topology=Topology(mesh=mesh, shard=shard)))
    want_plan = {"both": (True, True), "r": (True, False),
                 "s": (False, True), "none": None}[shard]
    assert sess.plan == want_plan
    got = sess.infer_step(buf, valid)
    np.testing.assert_array_equal(np.asarray(got.predictions),
                                  np.asarray(oracle.predictions))
    np.testing.assert_allclose(np.asarray(got.e_clause_lanes),
                               np.asarray(oracle.e_clause_lanes), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got.e_class_lanes),
                               np.asarray(oracle.e_class_lanes), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got.e_clause_lanes)[6:], 0.0)


@multi_device
@pytest.mark.parametrize("shard", ["both", "r", "s", "none"])
def test_packed_sessions_across_shard_plans(shard):
    """packing='2bit' under all four forced shard plans: the packed
    clause operand rides the same psum lowering (bits shard on the
    R axis like the currents they encode; the dequant levels replicate),
    so predictions AND per-lane energy bills match the single-device
    packed kernel — the compressed-datapath acceptance sweep."""
    mesh = _mesh_or_skip(2)
    B, K = 8, 300
    lit, sys_ = _make_system(B, K, 120, 7, 4, 80, 3, 40, 4, 30, seed=41)
    buf = np.ones((B, K), np.int8)
    buf[:6] = np.asarray(lit[:6])
    valid = np.zeros((B,), bool)
    valid[:6] = True
    single = sys_.compile(RuntimeSpec(
        backend="pallas", packing="2bit", metering="fused",
        capacity=B)).infer_step(buf, valid)
    sess = sys_.compile(RuntimeSpec(
        backend="xla", packing="2bit", metering="fused", capacity=B,
        topology=Topology(mesh=mesh, shard=shard)))
    want_plan = {"both": (True, True), "r": (True, False),
                 "s": (False, True), "none": None}[shard]
    assert sess.plan == want_plan
    got = sess.infer_step(buf, valid)
    np.testing.assert_array_equal(np.asarray(got.predictions),
                                  np.asarray(single.predictions))
    assert (np.asarray(got.predictions)[6:] == -1).all()
    np.testing.assert_allclose(np.asarray(got.e_clause_lanes),
                               np.asarray(single.e_clause_lanes),
                               rtol=1e-4, atol=0.0)
    np.testing.assert_allclose(np.asarray(got.e_class_lanes),
                               np.asarray(single.e_class_lanes),
                               rtol=1e-4, atol=0.0)
    np.testing.assert_array_equal(np.asarray(got.e_clause_lanes)[6:], 0.0)


@multi_device
def test_packed_predict_parity_on_mesh():
    """Unmetered packed predict from a sharded topology matches the
    unpacked einsum oracle on argmax (quantization preserves the CSA
    decisions; sharding preserves the quantized physics)."""
    mesh = _mesh_or_skip(2)
    lit, sys_ = _make_system(16, 300, 120, 7, 4, 80, 3, 40, 4, 30, seed=43)
    sharded = sys_.compile(RuntimeSpec(
        backend="xla", packing="2bit", metering="off",
        topology=Topology(mesh=mesh)))
    assert sharded.plan == (True, True)
    base = sys_.compile(RuntimeSpec(backend="xla", metering="off"))
    np.testing.assert_array_equal(
        np.asarray(sharded.predict(lit).predictions),
        np.asarray(base.predict(lit).predictions))


@multi_device
def test_engine_on_sharded_mesh_bills_exactly():
    """IMPACTEngine serving from a sharded session: predictions match the
    single-device direct path and per-request energy attribution still
    sums exactly to the batch meter (ISSUE acceptance)."""
    mesh = _mesh_or_skip(2)
    lit, sys_ = _make_system(24, 300, 120, 7, 4, 80, 3, 40, 4, 30, seed=17)
    session = sys_.compile(RuntimeSpec(
        backend="xla", capacity=8, topology=Topology(mesh=mesh)))
    eng = IMPACTEngine(session)
    assert eng.mesh is mesh            # engine inherits the session mesh
    preds, stats = eng.run(np.asarray(lit))
    direct = np.asarray(
        sys_.compile(RuntimeSpec(backend="xla")).predict(lit).predictions)
    np.testing.assert_array_equal(preds, direct)
    recs = eng.request_records
    assert len(recs) == 24 and all(r.e_read_j > 0 for r in recs)
    np.testing.assert_allclose(sum(r.e_read_j for r in recs),
                               stats["energy"].read_energy_j, rtol=1e-6)
    # per-STEP reports carry the area and a real TOPS/mm^2; the summed-
    # latency aggregate refuses (the ratio would shrink with sweep count)
    assert all(r.tops_per_mm2 > 0 for r in eng.reports)
    with pytest.raises(ValueError, match="area"):
        stats["energy"].tops_per_mm2


SMOKE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.impact import RuntimeSpec, Topology
    from repro.impact.yflash import I_CSA_THRESHOLD
    from repro.kernels import ops, ref
    from repro.launch.mesh import make_crossbar_mesh
    from repro.serve import IMPACTEngine
    from repro.sharding import crossbar
    import sys
    sys.path.insert(0, {tests_dir!r})
    from test_fused_impact import _make_system

    mesh = make_crossbar_mesh(n_model=2)      # (4 data, 2 model)
    lit, base = _make_system(16, 200, 60, 5, 2, 100, 2, 32, 2, 32, seed=7)
    want = ref.fused_impact_ref(lit, base.clause_i, base.nonempty,
                                base.class_i, thresh=I_CSA_THRESHOLD)
    got = ops.fused_impact(lit, base.clause_i, base.nonempty, base.class_i,
                           thresh=I_CSA_THRESHOLD, impl="xla", mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))

    # asymmetric R-only plan (S=3 does not divide the model axis)
    lit_a, asym = _make_system(8, 200, 60, 5, 2, 100, 2, 32, 3, 20, seed=9)
    assert crossbar.shard_plan(mesh, 2, 3) == (True, False)
    want_a = ref.fused_impact_ref(lit_a, asym.clause_i, asym.nonempty,
                                  asym.class_i, thresh=I_CSA_THRESHOLD)
    got_a = ops.fused_impact(lit_a, asym.clause_i, asym.nonempty,
                             asym.class_i, thresh=I_CSA_THRESHOLD,
                             impl="xla", mesh=mesh)
    np.testing.assert_allclose(np.asarray(got_a), np.asarray(want_a),
                               rtol=1e-6)

    session = base.compile(RuntimeSpec(backend="xla", capacity=16,
                                       topology=Topology(mesh=mesh)))
    eng = IMPACTEngine(session)
    preds, stats = eng.run(np.asarray(lit))
    direct = base.compile(RuntimeSpec(backend="xla")).predict(lit)
    np.testing.assert_array_equal(preds, np.asarray(direct.predictions))
    np.testing.assert_allclose(
        sum(r.e_read_j for r in eng.request_records),
        stats["energy"].read_energy_j, rtol=1e-6)

    # fused metering on the mesh == staged single-device oracle (per-lane
    # bills psummed once; free lanes bill zero)
    buf = np.ones((16, 200), np.int8)
    buf[:9] = np.asarray(lit[:9], np.int8)
    vd = np.zeros((16,), bool); vd[:9] = True
    st = base.compile(RuntimeSpec(backend="xla", metering="staged",
                                  capacity=16)).infer_step(buf, vd)
    fu = base.compile(RuntimeSpec(backend="xla", metering="fused",
                                  capacity=16,
                                  topology=Topology(mesh=mesh))
                      ).infer_step(buf, vd)
    np.testing.assert_array_equal(np.asarray(fu.predictions),
                                  np.asarray(st.predictions))
    np.testing.assert_allclose(np.asarray(fu.e_clause_lanes),
                               np.asarray(st.e_clause_lanes), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fu.e_class_lanes),
                               np.asarray(st.e_class_lanes), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(fu.e_clause_lanes)[9:], 0.0)

    # packed (2-bit) operands ride the same psum lowering: sharded packed
    # session == single-device packed kernel, preds and lane bills alike
    pk_one = base.compile(RuntimeSpec(backend="pallas",
                                      packing="2bit", metering="fused",
                                      capacity=16)).infer_step(buf, vd)
    pk_mesh = base.compile(RuntimeSpec(backend="xla", packing="2bit",
                                       metering="fused", capacity=16,
                                       topology=Topology(mesh=mesh))
                           ).infer_step(buf, vd)
    np.testing.assert_array_equal(np.asarray(pk_mesh.predictions),
                                  np.asarray(pk_one.predictions))
    np.testing.assert_allclose(np.asarray(pk_mesh.e_clause_lanes),
                               np.asarray(pk_one.e_clause_lanes),
                               rtol=1e-4, atol=0.0)
    print("SHARDED_SMOKE_OK", jax.device_count())
""")


def test_sharded_smoke_on_forced_host_devices():
    """One real 8-device run in the tier-1 lane (subprocess, because the
    XLA host-device flag must be set before jax initialises): parity of
    the shard_map lowering vs the oracle — including an asymmetric
    R-only plan — plus session-engine billing and a fused-metering
    sweep billed against the staged single-device oracle.  The full
    sweeps run in-process in the CI multi-device leg."""
    tests_dir = str(pathlib.Path(__file__).resolve().parent)
    r = subprocess.run(
        [sys.executable, "-c", SMOKE.format(tests_dir=tests_dir)],
        # JAX_PLATFORMS=cpu matters: without it, a host with libtpu
        # installed spends ~8 min of TPU-metadata retries in the scrubbed
        # subprocess env before falling back to CPU.
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", "/root"),
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert "SHARDED_SMOKE_OK" in r.stdout, (r.stdout[-2000:],
                                            r.stderr[-3000:])
