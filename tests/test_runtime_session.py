"""Compiled-session runtime: RuntimeSpec validation, backend registry
pluggability, compile-once semantics (retrace guard), InferenceResult
contents, one device->host transfer per call, and exact-parity
deprecation shims for the old per-call kwargs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.impact import (IMPACTConfig, InferenceResult, InferenceSession,
                          RuntimeSpec, SpecDeprecationWarning, Topology,
                          build_coresident, build_system)
from repro.impact import runtime as rt
from repro.impact.yflash import T_READ, V_READ
from repro.core import CoTMConfig
from repro.core.cotm import CoTMParams
from repro.kernels import backends
from repro.serve import IMPACTEngine


@pytest.fixture(scope="module")
def small_system():
    K, n, m, n_states = 64, 32, 4, 64
    cfg = CoTMConfig(n_literals=K, n_clauses=n, n_classes=m,
                     n_states=n_states)
    rng = np.random.default_rng(0)
    ta = np.where(rng.random((K, n)) < 0.1, n_states + 1, n_states)
    w = rng.integers(-20, 20, (m, n))
    params = CoTMParams(ta_state=jnp.asarray(ta, jnp.int32),
                        weights=jnp.asarray(w, jnp.int32))
    system = build_system(params, cfg, jax.random.key(0),
                          IMPACTConfig(variability=False, finetune=False))
    lits = rng.random((40, K)) < 0.5
    return system, lits


@pytest.fixture(scope="module")
def coresident_system(small_system):
    """Two copies of the small system on one grid, and a slot buffer
    whose lanes alternate between them."""
    system, lits = small_system
    combined, plan = build_coresident([system, system])
    buf = np.ones((8, combined.n_literals), np.int8)
    mids = np.arange(8, dtype=np.int32) % 2
    for i, sp in enumerate(plan.spans[m] for m in mids):
        buf[i, sp.lit_lo:sp.lit_hi] = lits[i]
    return combined, plan, buf, mids


# -- backend registry --------------------------------------------------------

def test_registry_contents_and_errors():
    # The spec's metering and packing fields choose the kernel variant,
    # so the registry holds one kernel lowering and one oracle.
    assert set(backends.available_backends()) == {"pallas", "xla"}
    assert backends.get_backend("xla").reference
    assert not backends.get_backend("pallas").reference
    assert isinstance(backends.get_backend("pallas"),
                      backends.PallasBackend)
    with pytest.raises(ValueError, match="unknown backend"):
        backends.get_backend("mythical")
    with pytest.raises(ValueError, match="already registered"):
        backends.register_backend(backends.XLABackend())
    with pytest.raises(ValueError, match="non-empty"):
        backends.register_backend(backends.Backend())


def test_registered_backend_plugs_into_sessions(small_system):
    """A third backend slots into every entry point by registration alone
    — no call-site changes (the registry acceptance criterion).  This one
    delegates to the oracle, so outputs must match the xla session."""
    system, lits = small_system

    class ShadowXLA(backends.XLABackend):
        name = "xla-shadow-test"

    backends.register_backend(ShadowXLA())
    try:
        shadow = system.compile(RuntimeSpec(backend="xla-shadow-test",
                                            capacity=8))
        plain = system.compile(RuntimeSpec(backend="xla", capacity=8))
        np.testing.assert_array_equal(
            np.asarray(shadow.predict(lits[:8]).predictions),
            np.asarray(plain.predict(lits[:8]).predictions))
        r_s = shadow.infer_with_report(lits[:8]).report
        r_p = plain.infer_with_report(lits[:8]).report
        np.testing.assert_allclose(r_s.read_energy_j, r_p.read_energy_j)
    finally:
        backends.unregister_backend("xla-shadow-test")
    assert "xla-shadow-test" not in backends.available_backends()
    with pytest.raises(ValueError, match="not registered"):
        backends.unregister_backend("xla-shadow-test")


def test_interpret_resolver_policy():
    """The shared shape-policy hook: None means interpret off-TPU for
    kernel backends; reference backends have nothing to interpret."""
    pallas = backends.get_backend("pallas")
    on_tpu = jax.default_backend() == "tpu"
    assert pallas.resolve_interpret(None) == (not on_tpu)
    assert pallas.resolve_interpret(True) is True
    assert pallas.resolve_interpret(False) is False
    assert backends.get_backend("xla").resolve_interpret(None) is False


# -- spec validation ---------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ValueError, match="metering"):
        RuntimeSpec(metering="always")
    # every declared metering mode is a valid spec
    for mode in ("off", "staged", "fused"):
        assert RuntimeSpec(metering=mode).metering == mode
    with pytest.raises(ValueError, match="precision"):
        RuntimeSpec(precision="bf16")
    with pytest.raises(ValueError, match="packing"):
        RuntimeSpec(packing="4bit")
    for packing in ("none", "2bit"):
        assert RuntimeSpec(packing=packing).packing == packing
    with pytest.raises(ValueError, match="capacity"):
        RuntimeSpec(capacity=0)
    with pytest.raises(ValueError, match="batch_sizes"):
        RuntimeSpec(batch_sizes=(0,))
    with pytest.raises(ValueError, match="shard mode"):
        Topology(shard="diagonal")
    # specs are hashable values: equal fields => equal keys
    assert RuntimeSpec(backend="xla") == RuntimeSpec(backend="xla")
    assert hash(RuntimeSpec()) == hash(RuntimeSpec())


def test_compile_validates_spec(small_system):
    system, _ = small_system
    with pytest.raises(ValueError, match="unknown backend"):
        system.compile(RuntimeSpec(backend="mythical"))
    with pytest.raises(ValueError, match="mesh"):
        system.compile(RuntimeSpec(topology=Topology(shard="both")))


def test_compile_caches_per_spec(small_system):
    """compile() is idempotent: the same spec (as a value, not an object)
    resolves to the SAME session, so sessions are safe to re-derive."""
    system, _ = small_system
    a = system.compile(RuntimeSpec(backend="xla", capacity=8))
    b = system.compile(RuntimeSpec(backend="xla", capacity=8))
    assert a is b
    assert isinstance(a, InferenceSession)
    assert a is not system.compile(RuntimeSpec(backend="xla", capacity=4))


# -- packed sessions ---------------------------------------------------------

def test_packed_session_parity_and_input_bytes(small_system):
    """packing='2bit' compiles the packed executable: argmax parity with
    the unpacked session, operand footprint down >= 4x (the layout-level
    half of the perf gate's compressed section), and the spec value
    surfaces in repr for debuggability."""
    system, lits = small_system
    base = system.compile(RuntimeSpec(backend="pallas", metering="off"))
    packed = system.compile(RuntimeSpec(backend="pallas",
                                        packing="2bit", metering="off"))
    np.testing.assert_array_equal(
        np.asarray(packed.predict(lits[:16]).predictions),
        np.asarray(base.predict(lits[:16]).predictions))
    ratio = base.input_bytes("predict", 16) / packed.input_bytes("predict", 16)
    assert ratio >= 4.0, ratio
    assert "packing='2bit'" in repr(packed)
    assert "packing='none'" in repr(base)


def test_packing_is_backend_agnostic(small_system):
    """packing='2bit' is a spec value every registered backend serves —
    the Pallas packed kernel and the einsum oracle — and they agree on
    argmax (they consume the same quantized operand, so scores differ
    only by float association)."""
    system, lits = small_system
    preds = {
        impl: np.asarray(
            system.compile(RuntimeSpec(backend=impl, packing="2bit",
                                       metering="off"))
            .predict(lits[:16]).predictions)
        for impl in ("xla", "pallas")}
    np.testing.assert_array_equal(preds["xla"], preds["pallas"])


def test_packed_session_metered_report(small_system):
    """Metering on a packed session works end to end and bills positive
    joules (the quantized currents, not zeros)."""
    system, lits = small_system
    rep = system.compile(RuntimeSpec(backend="pallas",
                                     packing="2bit", metering="fused")) \
        .infer_with_report(lits[:8]).report
    assert rep.read_energy_j > 0
    assert rep.datapoints == 8


# -- compile-once semantics (the retrace guard) ------------------------------

def test_session_precompiles_spec_shapes(small_system):
    system, _ = small_system
    sess = system.compile(RuntimeSpec(backend="xla", capacity=8,
                                      batch_sizes=(4, 12)))
    assert sess.is_compiled("infer_step", 8)
    assert sess.is_compiled("predict", 4)
    assert sess.is_compiled("predict", 12)
    assert sess.trace_count == 3
    assert sess.capacity == 8 and sess.meters_energy


def test_retrace_guard_across_serving(small_system):
    """The compile-once acceptance test: after session build (+ declared
    shapes), repeated predict calls, arbitrary admission patterns, and
    whole engine sweeps trigger ZERO new traces — pinned by the
    session's trace counters (each counter bumps exactly when a python
    body is traced for compilation)."""
    system, lits = small_system
    sess = system.compile(RuntimeSpec(backend="xla", capacity=8,
                                      batch_sizes=(4,)))
    built = sess.trace_count                   # capacity + batch_sizes
    assert built == 2

    # repeated predict at a compiled shape: no new traces
    for i in range(3):
        sess.predict(lits[i:i + 4])
    assert sess.trace_count == built

    # a NEW batch shape compiles exactly once, then caches
    sess.predict(lits[:6])
    assert sess.trace_count == built + 1
    sess.predict(lits[6:12])
    assert sess.trace_count == built + 1

    # every admission pattern reuses the one slot-table executable
    buf = np.ones((8, system.n_literals), np.int8)
    for k in (1, 3, 8, 2):
        valid = np.zeros((8,), bool)
        valid[:k] = True
        buf[:k] = lits[:k]
        sess.infer_step(buf, valid)
    assert sess.trace_count == built + 1

    # engine sweeps (admit/release/partial tails) ride the same
    # executable: a full burst adds zero traces
    eng = IMPACTEngine(sess)
    preds, stats = eng.run(lits[:20])
    assert stats["cold_batches"] == 0
    assert sess.trace_count == built + 1

    # metered report at a fresh shape is the only remaining compile
    sess.infer_with_report(lits[:5])
    assert sess.trace_count == built + 2
    sess.infer_with_report(lits[5:10])
    assert sess.trace_count == built + 2


def test_session_canonicalizes_caller_dtypes(small_system):
    """bool / int8 / float {0,1} literals hit the SAME executable — the
    session casts once instead of letting caller dtypes fragment the
    AOT cache (and the results agree exactly)."""
    system, lits = small_system
    sess = system.compile(RuntimeSpec(backend="xla"))
    base = np.asarray(sess.predict(lits[:8]).predictions)   # np.bool_
    tc = sess.trace_count
    np.testing.assert_array_equal(
        np.asarray(sess.predict(lits[:8].astype(np.int8)).predictions),
        base)
    np.testing.assert_array_equal(
        np.asarray(sess.predict(lits[:8].astype(np.float32)).predictions),
        base)
    np.testing.assert_array_equal(
        np.asarray(sess.predict(jnp.asarray(lits[:8])).predictions), base)
    assert sess.trace_count == tc


# -- InferenceResult ---------------------------------------------------------

def test_inference_result_contents(small_system):
    system, lits = small_system
    sess = system.compile(RuntimeSpec(backend="xla", capacity=8))
    pred = sess.predict(lits[:8])
    assert isinstance(pred, InferenceResult)
    assert pred.scores.shape == (8, system.n_classes)
    assert pred.report is None and pred.e_clause_lanes is None
    np.testing.assert_array_equal(
        np.asarray(pred.predictions),
        np.asarray(jnp.argmax(pred.scores, axis=-1)))

    valid = np.ones((8,), bool)
    step = sess.infer_step(np.asarray(lits[:8], np.int8), valid)
    assert step.e_clause_lanes.shape == (8,)
    assert step.e_class_lanes.shape == (8,)
    assert step.report is None

    rep = sess.infer_with_report(lits[:8])
    assert rep.report.datapoints == 8
    assert rep.report.read_energy_j > 0
    with pytest.raises(dataclasses.FrozenInstanceError):
        rep.report = None


def test_metering_off_blocks_reports_and_zeros_lanes(small_system):
    system, lits = small_system
    sess = system.compile(RuntimeSpec(backend="xla", metering="off",
                                      capacity=8))
    assert not sess.meters_energy
    step = sess.infer_step(np.asarray(lits[:8], np.int8),
                           np.ones((8,), bool))
    np.testing.assert_array_equal(np.asarray(step.e_clause_lanes), 0.0)
    with pytest.raises(RuntimeError, match="metering"):
        sess.infer_with_report(lits[:8])


# -- one device->host transfer per call --------------------------------------

def _session_case(small_system, coresident_system, metering, packing,
                  coresident):
    """(session, 8-lane literal buffer, model_ids kwargs) for one case."""
    spec = RuntimeSpec(backend="xla", metering=metering, packing=packing,
                       capacity=8)
    if coresident:
        combined, plan, buf, mids = coresident_system
        sess = combined.compile(dataclasses.replace(spec, coresident=plan))
        return sess, buf, dict(model_ids=mids)
    system, lits = small_system
    return system.compile(spec), np.asarray(lits[:8], np.int8), {}


CASES = pytest.mark.parametrize(
    "metering,packing,coresident",
    [(m, p, c) for m in ("staged", "fused") for p in ("none", "2bit")
     for c in (False, True)])


@CASES
def test_fetch_is_one_transfer_of_the_device_fields(
        small_system, coresident_system, metering, packing, coresident):
    """``fetch`` returns exactly what ``np.asarray`` of the three device
    fields gives — sentinels on invalid lanes included — in one transfer
    counted by ``fetch_count``."""
    sess, buf, kw = _session_case(small_system, coresident_system,
                                  metering, packing, coresident)
    valid = np.array([1, 1, 0, 1, 0, 1, 1, 0], bool)
    res = sess.infer_step(buf, valid, **kw)
    before = sess.fetch_count
    preds, e_cl, e_cs = sess.fetch(res)
    assert sess.fetch_count == before + 1
    assert preds.dtype == np.int32
    assert e_cl.dtype == e_cs.dtype == np.float64
    np.testing.assert_array_equal(preds, np.asarray(res.predictions))
    np.testing.assert_array_equal(
        e_cl, np.asarray(res.e_clause_lanes, np.float64))
    np.testing.assert_array_equal(
        e_cs, np.asarray(res.e_class_lanes, np.float64))
    assert (preds[~valid] == -1).all() and (preds[valid] >= 0).all()
    assert (e_cl[~valid] == 0).all() and (e_cl[valid] > 0).all()


def test_fetch_of_a_rebuilt_result_reads_its_fields(small_system):
    """A result rebuilt with other fields carries no host buffer, so
    ``fetch`` cannot serve the executable's stale view of them: it
    copies the fields themselves, one transfer each."""
    system, lits = small_system
    sess = system.compile(RuntimeSpec(backend="xla", capacity=8))
    res = sess.infer_step(np.asarray(lits[:8], np.int8), np.ones((8,), bool))
    p = res.predictions
    alt = dataclasses.replace(
        res, predictions=p.at[0].set((p[0] + 1) % system.n_classes))
    assert res.host_buffer is not None and alt.host_buffer is None
    before = sess.fetch_count
    preds, e_cl, e_cs = sess.fetch(alt)
    assert sess.fetch_count == before + 3
    np.testing.assert_array_equal(preds, np.asarray(alt.predictions))
    assert preds[0] != np.asarray(p)[0]
    np.testing.assert_array_equal(
        e_cl, np.asarray(res.e_clause_lanes, np.float64))
    np.testing.assert_array_equal(
        e_cs, np.asarray(res.e_class_lanes, np.float64))


@CASES
def test_report_joules_match_the_eager_product(
        small_system, coresident_system, metering, packing, coresident):
    """``infer_with_report`` bills ``float(V_READ * i * T_READ)`` of the
    executable's summed currents bit for bit, in one transfer."""
    sess, buf, kw = _session_case(small_system, coresident_system,
                                  metering, packing, coresident)
    valid = np.array([1, 1, 1, 0, 1, 1, 0, 1], bool)
    before = sess.fetch_count
    rep = sess.infer_with_report(buf, valid, **kw)
    assert sess.fetch_count == before + 1
    mids = ((jnp.asarray(kw["model_ids"]),) if kw else ())
    preds, i_cl, i_cs = jax.jit(sess._report_sums)(
        jnp.asarray(buf), jnp.asarray(valid), *mids, *sess._operands())
    assert rep.report.clause_energy_j == float(V_READ * i_cl * T_READ)
    assert rep.report.class_energy_j == float(V_READ * i_cs * T_READ)
    assert rep.report.clause_energy_j > 0
    np.testing.assert_array_equal(np.asarray(rep.predictions),
                                  np.asarray(preds))
    assert isinstance(rep.predictions, jax.Array)


def test_report_without_valid_uploads_no_mask(small_system):
    """``infer_with_report(valid=None)`` makes its all-valid mask on the
    device once per batch size: later calls upload nothing (device
    literals in, so any host->device transfer would be the mask)."""
    system, lits = small_system
    sess = system.compile(RuntimeSpec(backend="xla", metering="fused"))
    rows = jnp.asarray(lits[:8], jnp.int8)
    first = sess.infer_with_report(rows).report
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        again = sess.infer_with_report(rows).report
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="Disallowed host-to-device"):
            sess.infer_with_report(rows, valid=np.ones((8,), bool))
    assert again == first and again.datapoints == 8


# -- bit-packed literal upload -----------------------------------------------

@pytest.fixture(scope="module")
def r5_system():
    """K=597 literals (not a multiple of 8) on 128x128 tiles: R=5 literal
    row-shards, as the R=5 grids of test_fused_impact's SHARD_SHAPES."""
    K, n, m, n_states = 597, 300, 2, 64
    cfg = CoTMConfig(n_literals=K, n_clauses=n, n_classes=m,
                     n_states=n_states)
    rng = np.random.default_rng(5)
    ta = np.where(rng.random((K, n)) < 0.01, n_states + 1, n_states)
    w = rng.integers(-20, 20, (m, n))
    params = CoTMParams(ta_state=jnp.asarray(ta, jnp.int32),
                        weights=jnp.asarray(w, jnp.int32))
    system = build_system(params, cfg, jax.random.key(5), IMPACTConfig(
        max_tile_rows=128, max_tile_cols=128, max_class_rows=128,
        variability=False, finetune=False))
    assert system.clause_i.shape[0] == 5
    return system, rng.random((512, K)) < 0.5


@pytest.mark.parametrize("K", [1, 7, 8, 31, 32, 33, 64, 597])
def test_literal_packing_round_trip(K):
    """Bit k % 32 of word k // 32 is literal k, the bits past K are 0, the
    host and device packers agree, and the traced unpack gives back the
    int8 rows, whatever {0,1} dtype the rows came in."""
    rng = np.random.default_rng(K)
    rows = rng.random((6, K)) < 0.5
    rows[0] = True
    words = rt.pack_literals_host(rows)
    assert words.dtype == np.uint32
    assert words.shape == (6, rt.literal_words(K)) == (6, -(-K // 32))
    k = np.arange(K)
    np.testing.assert_array_equal((words[:, k // 32] >> (k % 32)) & 1, rows)
    # An all-ones row sets exactly K bits: the padding bits are 0.
    assert sum(int(w).bit_count() for w in words[0]) == K
    for dt in (np.int8, np.int32, np.float32):
        np.testing.assert_array_equal(
            rt.pack_literals_host(rows.astype(dt)), words)
        np.testing.assert_array_equal(
            np.asarray(jax.jit(rt.pack_literals)(jnp.asarray(rows, dt))),
            words)
    back = jax.jit(rt.unpack_literals, static_argnums=1)(
        jnp.asarray(words), K)
    assert back.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(back), rows.astype(np.int8))


def _parity_session(small_system, r5_system, coresident_system, grid,
                    route):
    """(session, (8, K) bool rows, model_ids kwargs) for one parity case."""
    spec = RuntimeSpec(backend="pallas",
                       metering="staged" if route == "staged" else "fused",
                       packing="2bit" if route == "2bit" else "none")
    if route == "coresident":
        combined, plan, buf, mids = coresident_system
        sess = combined.compile(RuntimeSpec(backend="pallas",
                                            coresident=plan))
        return sess, buf.astype(bool), dict(model_ids=mids)
    system, lits = small_system if grid == "k64" else r5_system
    return system.compile(spec), np.asarray(lits[:8], bool), {}


PARITY = pytest.mark.parametrize(
    "grid,route,dtype",
    [(g, r, d) for g, routes in (("k64", ("fused", "staged", "2bit",
                                          "coresident")),
                                 ("k597", ("fused", "staged", "2bit")))
     for r in routes for d in ("bool", "int8", "float32", "device")])


@PARITY
def test_packed_report_matches_the_int8_path(
        small_system, r5_system, coresident_system, grid, route, dtype):
    """``infer_with_report`` on bit-packed words gives the predictions and
    both joule figures of the same executable body fed int8 rows, bit for
    bit: host rows as bool, int8 or float32, and a device array, with
    padding lanes in the batch, on every route."""
    sess, rows, kw = _parity_session(small_system, r5_system,
                                     coresident_system, grid, route)
    assert sess.routes["infer_with_report"][0] == (
        "staged" if route in ("staged", "coresident")
        else "packed" if route == "2bit" else "fused")
    valid = np.array([1, 1, 0, 1, 1, 0, 1, 0], bool)
    mids = ((jnp.asarray(kw["model_ids"]),) if kw else ())
    preds, i_cl, i_cs = jax.jit(sess._report_sums)(
        jnp.asarray(rows, jnp.int8), jnp.asarray(valid), *mids,
        *sess._operands())
    literals = (jnp.asarray(rows) if dtype == "device"
                else rows.astype(dtype))
    uploads = sess.packed_uploads
    rep = sess.infer_with_report(literals, valid, **kw)
    assert sess.packed_uploads == uploads + (dtype != "device")
    np.testing.assert_array_equal(np.asarray(rep.predictions),
                                  np.asarray(preds))
    assert (np.asarray(rep.predictions)[~valid] == -1).all()
    assert rep.report.clause_energy_j == float(V_READ * i_cl * T_READ)
    assert rep.report.class_energy_j == float(V_READ * i_cs * T_READ)
    assert rep.report.clause_energy_j > 0 and rep.report.datapoints == 5


@pytest.mark.parametrize("grid", ["k64", "k597"])
def test_literal_upload_counters(small_system, r5_system, grid):
    """One host (512, K) report call uploads ceil(K/32) words a row and
    counts one packed upload; ``input_bytes`` prices the same words.  A
    device batch uploads nothing, through either entry, and a host batch
    through ``infer_step`` ships its int8 rows."""
    system, lits = small_system if grid == "k64" else r5_system
    lits = np.resize(lits, (512, system.n_literals))
    K = system.n_literals
    sess = system.compile(RuntimeSpec(backend="xla", metering="fused",
                                      capacity=512))
    uploads, shipped = sess.packed_uploads, sess.literal_upload_bytes
    sess.infer_with_report(lits)
    assert sess.packed_uploads == uploads + 1
    words = 512 * -(-K // 32) * 4
    assert sess.literal_upload_bytes == shipped + words
    operands = sum(op.size * op.dtype.itemsize for op in sess._operands())
    assert sess.input_bytes("infer_with_report", 512) == (
        words + 512 + operands)
    assert sess.input_bytes("infer_step", 512) == 512 * K + 512 + operands

    on_device = jnp.asarray(lits, jnp.int8)
    valid = np.ones((512,), bool)
    uploads, shipped = sess.packed_uploads, sess.literal_upload_bytes
    sess.infer_step(on_device, valid)
    sess.infer_with_report(on_device)
    assert (sess.packed_uploads, sess.literal_upload_bytes) == (
        uploads, shipped)
    sess.infer_step(lits, valid)
    assert sess.packed_uploads == uploads
    assert sess.literal_upload_bytes == shipped + 512 * K


def test_device_batches_pack_once_per_shape(small_system):
    """A device batch is packed where it lies by a packer compiled once
    per (batch, dtype): the report executable keeps its one (entry,
    batch) key, and a repeat call compiles nothing."""
    system, lits = small_system
    sess = system.compile(RuntimeSpec(backend="xla", metering="fused",
                                      batch_sizes=(3,)))
    rows = jnp.asarray(lits[:8])
    sess.infer_with_report(rows)
    traces = sess.trace_count
    again = sess.infer_with_report(rows)
    assert sess.trace_count == traces
    assert sess.compiled_shapes("infer_with_report") == [
        ("infer_with_report", 8)]
    host = sess.infer_with_report(np.asarray(lits[:8]))
    assert sess.trace_count == traces
    np.testing.assert_array_equal(np.asarray(again.predictions),
                                  np.asarray(host.predictions))
    assert again.report == host.report
    for bad in (lits[:8, :-1], jnp.asarray(lits[:8, :-1]), lits[0]):
        with pytest.raises(ValueError, match="K=64"):
            sess.infer_with_report(bad)


# -- deprecation shims: old kwargs forward, warn, and agree exactly ----------

def test_predict_shim_parity_and_warning(small_system):
    system, lits = small_system
    want = np.asarray(system.compile(RuntimeSpec(backend="xla"))
                      .predict(lits[:8]).predictions)
    with pytest.warns(SpecDeprecationWarning, match="predict"):
        old = system.predict(jnp.asarray(lits[:8]), impl="xla")
    np.testing.assert_array_equal(np.asarray(old), want)
    # the bare call (no kwargs) is NOT deprecated: default-spec session
    bare = system.predict(jnp.asarray(lits[:8]))
    np.testing.assert_array_equal(
        np.asarray(bare),
        np.asarray(system.compile().predict(lits[:8]).predictions))


def test_infer_step_shim_parity_and_warning(small_system):
    system, lits = small_system
    buf = np.ones((8, system.n_literals), np.int8)
    buf[:3] = lits[:3]
    valid = np.zeros((8,), bool)
    valid[:3] = True
    sess = system.compile(RuntimeSpec(backend="xla", capacity=8))
    want = sess.infer_step(buf, valid)
    with pytest.warns(SpecDeprecationWarning, match="infer_step"):
        p, e_cl, e_cs = system.infer_step(jnp.asarray(buf), valid,
                                          impl="xla", meter=True)
    np.testing.assert_array_equal(np.asarray(p),
                                  np.asarray(want.predictions))
    np.testing.assert_array_equal(np.asarray(e_cl),
                                  np.asarray(want.e_clause_lanes))
    np.testing.assert_array_equal(np.asarray(e_cs),
                                  np.asarray(want.e_class_lanes))
    # bare call preserves the old meter=False default: zero energies
    p0, z_cl, z_cs = system.infer_step(jnp.asarray(buf), valid)
    np.testing.assert_array_equal(np.asarray(p0),
                                  np.asarray(want.predictions))
    np.testing.assert_array_equal(np.asarray(z_cl), 0.0)


def test_infer_with_report_shim_parity_and_warning(small_system):
    system, lits = small_system
    want = system.compile(RuntimeSpec(backend="xla")) \
        .infer_with_report(lits[:8])
    with pytest.warns(SpecDeprecationWarning, match="infer_with_report"):
        preds, report = system.infer_with_report(jnp.asarray(lits[:8]),
                                                 impl="xla")
    np.testing.assert_array_equal(np.asarray(preds),
                                  np.asarray(want.predictions))
    assert report.read_energy_j == want.report.read_energy_j
    assert report.datapoints == want.report.datapoints
    assert report.latency_s == want.report.latency_s


def test_engine_shim_parity_and_warning(small_system):
    system, lits = small_system
    sess = system.compile(RuntimeSpec(backend="xla", metering="off",
                                      capacity=16))
    want, _ = IMPACTEngine(sess).run(lits)
    with pytest.warns(SpecDeprecationWarning, match="IMPACTEngine"):
        legacy = IMPACTEngine(system, impl="xla", max_batch=16,
                              meter_energy=False)
    got, stats = legacy.run(lits)
    np.testing.assert_array_equal(got, want)
    assert legacy.session is sess      # same spec => same cached session
    # a bare IMPACTEngine(system) is the supported convenience form
    conv = IMPACTEngine(system, max_batch=16)
    assert conv.capacity == 16 and conv.meter_energy
