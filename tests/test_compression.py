"""train.compression: clause pruning (in-process) and int8 gradient
compression (subprocess with 8 host devices — the main test process must
keep seeing the single real CPU device)."""
import pathlib
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.impact import RuntimeSpec
from repro.impact.yflash import I_CSA_THRESHOLD
from repro.kernels import ref
from repro.train.compression import PruneStats, prune_clauses

from test_fused_impact import _make_system

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


# -- clause pruning ----------------------------------------------------------

def _calib_system(seed=0):
    """System + calibration batch with a CRAFTED duplicate clause column
    (column 5 copies column 3's cells) and a literal mix that leaves some
    clauses never firing — both pruning reductions exercised at once."""
    lit, sys_ = _make_system(64, 100, 80, 6, 2, 64, 2, 50, 2, 50, seed=seed)
    ci = np.asarray(sys_.clause_i).copy()
    cg = np.asarray(sys_.clause_g).copy()
    ci[:, 0, :, 5] = ci[:, 0, :, 3]
    cg[:, 0, :, 5] = cg[:, 0, :, 3]
    import dataclasses as _dc
    sys_ = _dc.replace(sys_, clause_i=jnp.asarray(ci),
                       clause_g=jnp.asarray(cg))
    return lit, sys_


def test_prune_clauses_stats_and_parity():
    lit, sys_ = _calib_system()
    pruned, stats = prune_clauses(sys_, lit)
    assert isinstance(stats, PruneStats)
    n_nonempty = int(np.asarray(sys_._nonempty_eff()).sum())
    # every nonempty column is accounted for exactly once
    assert stats.n_effective + stats.n_never_fired + stats.n_duplicates \
        == n_nonempty
    assert stats.n_duplicates >= 1          # the crafted copy was merged
    assert stats.n_never_fired >= 1
    assert 0 < stats.n_effective < n_nonempty
    assert stats.calibration_batch == 64
    assert stats.energy_per_effective_clause_j > 0
    # the record rides the system for downstream benchmarks
    import dataclasses as _dc
    assert pruned.encode_stats["pruning"] == _dc.asdict(stats)
    # prediction parity on the calibration batch (exact: a never-fired
    # clause contributes nothing there; the merged duplicate's class rows
    # were summed and its currents are identical to the survivor's)
    np.testing.assert_array_equal(
        np.asarray(pruned.compile(RuntimeSpec(backend="xla"))
                   .predict(lit).predictions),
        np.asarray(sys_.compile(RuntimeSpec(backend="xla"))
                   .predict(lit).predictions))


def test_prune_erases_retired_columns_physically():
    """Retired columns stop existing at the device level: currents and
    conductances zeroed, nonempty cleared — so they draw no leakage and
    the energy meter bills strictly less than the unpruned system."""
    lit, sys_ = _calib_system(seed=1)
    pruned, stats = prune_clauses(sys_, lit)
    ne_old = np.asarray(sys_._nonempty_eff())
    ne_new = np.asarray(pruned._nonempty_eff())
    dead = ne_old & ~ne_new
    assert dead.sum() == stats.n_never_fired + stats.n_duplicates
    C, tc = sys_.clause_i.shape[1], sys_.clause_i.shape[3]
    dead_cols = dead.reshape(C, tc)
    assert (np.asarray(pruned.clause_i)
            .transpose(1, 3, 0, 2)[dead_cols] == 0).all()
    assert (np.asarray(pruned.clause_g)
            .transpose(1, 3, 0, 2)[dead_cols] == 0).all()
    def clause_joules(s):
        _, i_cl, _ = ref.fused_impact_metered_ref(
            lit, s.clause_i, s._nonempty_eff(), s.class_i,
            thresh=I_CSA_THRESHOLD)
        return float(np.asarray(i_cl).sum())

    assert clause_joules(pruned) < clause_joules(sys_)


def test_prune_without_merge_keeps_duplicates():
    lit, sys_ = _calib_system(seed=2)
    _, merged = prune_clauses(sys_, lit)
    pruned, stats = prune_clauses(sys_, lit, merge_duplicates=False)
    assert stats.n_duplicates == 0
    assert stats.n_effective == merged.n_effective + merged.n_duplicates
    # class crossbar untouched without the merge
    np.testing.assert_array_equal(np.asarray(pruned.class_i),
                                  np.asarray(sys_.class_i))


def test_prune_degenerate_nothing_fires():
    """All-zero literals violate every clause (drive = 1 everywhere), so
    nothing fires: every nonempty column retires and the re-anchored
    energy figure reports 0.0 instead of dividing by zero."""
    lit, sys_ = _make_system(8, 100, 50, 4, 2, 64, 1, 64, 1, 64, seed=3)
    zeros = jnp.zeros_like(lit)
    pruned, stats = prune_clauses(sys_, zeros)
    assert stats.n_effective == 0
    assert stats.energy_per_effective_clause_j == 0.0
    assert not bool(np.asarray(pruned._nonempty_eff()).any())
    scores = np.asarray(pruned.compile(RuntimeSpec(backend="xla"))
                        .predict(lit).scores)
    np.testing.assert_array_equal(scores, 0.0)


def test_prune_stacks_with_packing():
    """The two compressions compose: a pruned system compiled with
    packing='2bit' stays argmax-parity with the unpruned oracle on the
    calibration batch."""
    lit, sys_ = _calib_system(seed=4)
    pruned, _ = prune_clauses(sys_, lit)
    np.testing.assert_array_equal(
        np.asarray(pruned.compile(RuntimeSpec(backend="pallas",
                                              packing="2bit"))
                   .predict(lit).predictions),
        np.asarray(sys_.compile(RuntimeSpec(backend="xla"))
                   .predict(lit).predictions))


# -- int8 gradient compression ----------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    import functools
    from jax.sharding import Mesh, PartitionSpec as P
    shard_map = functools.partial(jax.shard_map, check_vma=False)
    from repro.train.compression import int8_psum, compressed_grad_allreduce

    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    rng = np.random.default_rng(0)

    # --- int8_psum approximates the exact psum, all shards agree ---
    x = jnp.asarray(rng.normal(size=(8, 64, 33)), jnp.float32)
    f = shard_map(lambda v: int8_psum(v[0], "data")[None],
                  mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    got = np.asarray(f(x))
    want = np.asarray(x.sum(0))
    rel = np.abs(got[0] - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.02, rel
    assert np.allclose(got, got[0:1]), "shards disagree"

    # --- error feedback keeps cumulative bias bounded ---
    fstep = shard_map(
        lambda gg, ee: compressed_grad_allreduce(gg[0], ee[0], "data"),
        mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P("data")))
    e = jnp.zeros((8, 1, 128))
    acc_c = np.zeros(128); acc_t = np.zeros(128)
    for i in range(30):
        gi = jnp.asarray(rng.normal(size=(8, 1, 128)), jnp.float32) * 0.01
        tot, e = fstep(gi, e)
        acc_c += np.asarray(tot).reshape(128)
        acc_t += np.asarray(gi.sum(0)).reshape(128)
    drift = np.abs(acc_c - acc_t).max() / (np.abs(acc_t).max() + 1e-9)
    assert drift < 0.05, drift
    print("COMPRESSION_OK", rel, drift)
""")


@pytest.mark.slow
def test_int8_allreduce_and_error_feedback():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    assert "COMPRESSION_OK" in r.stdout, (r.stdout[-2000:],
                                          r.stderr[-3000:])
