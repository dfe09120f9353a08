"""Compile the served path's Pallas kernels for a TPU v5e at paper dims.

Interpret mode (how every other test runs the kernels on the CPU) cannot
see what the TPU's kernel compiler (Mosaic) refuses: unaligned blocks,
unsupported dtypes or vector ops, or more VMEM than a kernel may use.
Here each kernel is lowered with ``interpret=False`` and compiled for a
*described* v5e chip — libtpu compiles for it without one attached — at
the operand shapes the MNIST paper configuration (K=1568 literals,
n=500 clauses, m=10 classes on the default 2048x512 tile) produces after
the backends' neutral padding, and the fused kernels also at the
10,000-literal text CoTM's (K=n=10000, m=2: five row-shards).  For the
fused kernels the compiled executable is also read: the programmed
clause grid must reach the kernel as it lies, with no relayout on the
way.  Nothing runs, so these tests say nothing about results or times.

Only one process at a time may load libtpu, and the test workers import
every test file: so the topology is described inside a module fixture
(never at import), and all of these compiles live in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import vmem
from repro.impact.yflash import I_CSA_THRESHOLD
from repro.kernels import backends, packing

# Paper MNIST configuration on the default tile geometry
# (IMPACTConfig: 2048 tile rows, 512 tile columns, 2048 class rows).
K, N_CLAUSES, M = 1568, 500, 10
TILE_ROWS, TILE_COLS, CLASS_ROWS = 2048, 512, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile_for_chip(fn, *args):
    """AOT-compile ``fn`` for the described chip; -> compiled text.
    Raises whatever the TPU compiler raises."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the executable"
    return text


def _clause_grid_reaches_kernel(text, dims):
    """Assert that the compiled executable hands its f32 clause-grid
    parameter of shape ``dims`` to the Mosaic kernel as it lies.  Every
    instruction that reads it is the kernel's custom call, a bitcast
    (same bytes), or a copy that keeps the tiled layout and at most moves
    the grid to another memory space (XLA stages a grid that fits into
    VMEM), so no transpose, relayout copy, pad or fusion of it runs."""
    entry = text[text.index("\nENTRY "):]
    shape = re.escape("f32[" + ",".join(map(str, dims)) + "]")
    ((param, layout),) = re.findall(
        r"(%\S+) = (" + shape + r"\{[^}]*\}) parameter\(", entry)
    lines = [ln.split(" = ", 1) for ln in entry.splitlines() if " = " in ln]
    space = re.compile(r"S\(\d+\)")

    def readers(name):
        use = re.compile(re.escape(name) + r"[,)]")
        return [(lhs.split()[-1], rhs) for lhs, rhs in lines
                if use.search(rhs)]

    kernels, todo = 0, readers(param)
    while todo:
        name, rhs = todo.pop()
        if 'custom_call_target="tpu_custom_call"' in rhs:
            kernels += 1
            continue
        moved = re.match(r"\(?(\S+?\})[,)]?.* copy(-start|-done)?\(", rhs)
        assert (re.match(r"\S+ bitcast\(", rhs) or moved
                and space.sub("", moved.group(1)) == space.sub("", layout)), (
            f"the clause grid is relaid out before the kernel: {rhs}")
        todo += readers(name)
    assert kernels >= 1, "the clause grid never reaches the kernel"


def _system_operands(shape, batch):
    """The session's operands at paper dims, as shapes on one chip."""
    return (shape((batch, K), jnp.int8),
            shape((1, 1, TILE_ROWS, TILE_COLS), jnp.float32),
            shape((TILE_COLS,), jnp.bool_),
            shape((1, CLASS_ROWS, M), jnp.float32))


@pytest.mark.parametrize("batch", [128, 512])
@pytest.mark.parametrize("metered", [False, True],
                         ids=["fused_impact", "fused_impact_metered"])
def test_fused_impact_compiles_for_v5e(shape, batch, metered):
    lit, clause_i, nonempty, class_i = _system_operands(shape, batch)
    bk = backends.get_backend("pallas")
    entry = bk.fused_impact_metered if metered else bk.fused_impact
    text = _compile_for_chip(
        lambda *a: entry(*a, thresh=I_CSA_THRESHOLD, interpret=False),
        lit, clause_i, nonempty, class_i)
    _clause_grid_reaches_kernel(text, (1, 1, TILE_ROWS, TILE_COLS))
    ws = vmem.fused_working_set(R=1, C=1, tr=TILE_ROWS, tc=TILE_COLS, M=M,
                                metered=metered)
    # The clause grid's 512 columns, not the class grid's 2048 rows.
    assert ws.column_blocks == 2
    assert ws.total_bytes <= vmem.DEFAULT_VMEM_BUDGET_BYTES, ws


# The 10,000-literal text CoTM (bench/configs/imdb-cotm.json) on the same
# tiles: R=5 literal row-shards, C=20 clause column tiles, S=5 class shards.
IMDB_K, IMDB_N, IMDB_M, IMDB_R, IMDB_C, IMDB_S = 10000, 10000, 2, 5, 20, 5


@pytest.mark.parametrize("batch", [128, 512])
@pytest.mark.parametrize("metered", [False, True],
                         ids=["fused_impact", "fused_impact_metered"])
def test_fused_impact_compiles_for_v5e_at_imdb_widths(shape, batch,
                                                      metered):
    """Each grid step holds one 2048-row shard, so five shards compile
    within the default VMEM budget (all five in one block did not: 20-30
    MiB of scoped VMEM against 16), and the kernel reads the 0.42 GB
    (R, C, tr, tc) grid where it is programmed."""
    lit = shape((batch, IMDB_K), jnp.int8)
    clause_i = shape((IMDB_R, IMDB_C, TILE_ROWS, TILE_COLS), jnp.float32)
    nonempty = shape((IMDB_C * TILE_COLS,), jnp.bool_)
    class_i = shape((IMDB_S, CLASS_ROWS, IMDB_M), jnp.float32)
    bk = backends.get_backend("pallas")
    entry = bk.fused_impact_metered if metered else bk.fused_impact
    text = _compile_for_chip(
        lambda *a: entry(*a, thresh=I_CSA_THRESHOLD, interpret=False),
        lit, clause_i, nonempty, class_i)
    # The kernel's stable name, which the device trace reports.
    name = "fused_impact_metered" if metered else "fused_impact"
    assert f"%{name}." in text
    _clause_grid_reaches_kernel(text,
                                (IMDB_R, IMDB_C, TILE_ROWS, TILE_COLS))
    ws = vmem.fused_working_set(R=IMDB_R, C=IMDB_C, tr=TILE_ROWS,
                                tc=TILE_COLS, M=IMDB_M, metered=metered)
    assert ws.column_blocks == IMDB_C * TILE_COLS // 256
    assert ws.total_bytes <= vmem.DEFAULT_VMEM_BUDGET_BYTES, ws


def test_packed_report_compiles_for_v5e_at_imdb_widths(shape):
    """The session's 512-row ``infer_with_report`` executable on the text
    CoTM's grid takes the literals as (512, 313) uint32 words, and every
    value between that parameter and the kernel's drive operand (the
    unpacked int8 rows among them) stays in VMEM, memory space S(1): the
    unpack adds no (512, 10000) buffer in HBM."""
    from repro.impact import IMPACTConfig, RuntimeSpec
    from repro.impact.pipeline import IMPACTSystem

    grid = shape((IMDB_R, IMDB_C, TILE_ROWS, TILE_COLS), jnp.float32)
    classes = shape((IMDB_S, CLASS_ROWS, IMDB_M), jnp.float32)
    system = IMPACTSystem(
        clause_g=grid, nonempty=shape((IMDB_C * TILE_COLS,), jnp.bool_),
        class_g=classes, clause_i=grid, class_i=classes,
        n_literals=IMDB_K, n_clauses=IMDB_N, n_classes=IMDB_M,
        cfg=IMPACTConfig(variability=False, finetune=False),
        encode_stats={})
    session = system.compile(RuntimeSpec(backend="pallas", metering="fused",
                                         interpret=False))
    text = session._exe("infer_with_report", 512).as_text()
    assert "%fused_impact_metered." in text
    entry = text[text.index("\nENTRY "):]
    (words,) = re.findall(r"(%\S+) = u32\[512,313\]\{[^}]*\} parameter\(0\)",
                          entry)
    assert not re.search(r"s8\[512,10000\]\{[^}]*\} parameter\(", entry)
    lines = [ln.split(" = ", 1) for ln in entry.splitlines() if " = " in ln]

    def readers(name):
        use = re.compile(re.escape(name) + r"[,)]")
        return [(lhs.split()[-1], rhs) for lhs, rhs in lines
                if use.search(rhs)]

    drives, todo = 0, [(words, r) for r in readers(words)]
    while todo:
        src, (name, rhs) = todo.pop()
        if 'custom_call_target="tpu_custom_call"' in rhs:
            assert f"custom-call({src}," in rhs, (
                f"the words reach the kernel other than as its drive: "
                f"{rhs[:160]}")
            drives += 1
            continue
        assert "S(1)}" in rhs.split(" ", 1)[0], (
            f"the literals pass through HBM before the kernel: {rhs[:160]}")
        todo += [(name, r) for r in readers(name)]
    assert drives == 1, "the words never reach the kernel's drive operand"


# Tiles whose row count is not a multiple of 128: the README's R=2/S=2
# split (784-row tiles) served on one chip, and 152x256 tiles.  The
# blocks then span the tile's full height, which Mosaic takes.
UNALIGNED = {"tile_rows_784": (1568, 2, 1, 784, 512, 2, 250, 10),
             "tile_152x256": (300, 2, 3, 152, 256, 1, 768, 3)}


@pytest.mark.parametrize("grid", list(UNALIGNED))
def test_fused_impact_metered_compiles_for_v5e_on_unaligned_rows(shape,
                                                                 grid):
    K_, R, C, tr, tc, S, sr, M_ = UNALIGNED[grid]
    bk = backends.get_backend("pallas")
    text = _compile_for_chip(
        lambda *a: bk.fused_impact_metered(*a, thresh=I_CSA_THRESHOLD,
                                           interpret=False),
        shape((128, K_), jnp.int8), shape((R, C, tr, tc), jnp.float32),
        shape((C * tc,), jnp.bool_), shape((S, sr, M_), jnp.float32))
    _clause_grid_reaches_kernel(text, (R, C, tr, tc))


@pytest.mark.parametrize("batch", [128, 512])
@pytest.mark.parametrize("metered", [False, True],
                         ids=["fused_impact_packed",
                              "fused_impact_packed_metered"])
def test_fused_impact_packed_compiles_for_v5e(shape, batch, metered):
    lit, _, nonempty, class_i = _system_operands(shape, batch)
    tr4 = packing.packed_rows(TILE_ROWS)
    packed = packing.PackedClause(
        bits=shape((1, 1, tr4, TILE_COLS), jnp.uint8),
        levels=shape((2,), jnp.float32))
    bk = backends.get_backend("pallas")
    ops_ = jax.eval_shape(
        lambda *a: bk._fused_impact_packed_operands(
            *a, tr=TILE_ROWS, block_b=128, block_n=256)[:5],
        lit, packed, nonempty, class_i)
    assert [o.shape for o in ops_] == [(1, 4, batch, 512), (1, 512, 2048),
                                       (1, 128), (1, 2048), (2048, 128)]
    entry = (bk.fused_impact_packed_metered if metered
             else bk.fused_impact_packed)
    _compile_for_chip(
        lambda *a: entry(*a, thresh=I_CSA_THRESHOLD, tr=TILE_ROWS,
                         interpret=False),
        lit, packed, nonempty, class_i)
    ws = vmem.packed_working_set(R=1, tr4=tr4, n_clause=TILE_COLS,
                                 class_rows=CLASS_ROWS, M=M, metered=metered)
    assert ws.total_bytes <= vmem.DEFAULT_VMEM_BUDGET_BYTES, ws


def test_ta_feedback_compiles_for_v5e(shape):
    """The online trainer's update kernel, at paper dims and an update
    batch of 64 rows (128 doubled feedback rows)."""
    b2 = 128
    row = lambda dt: shape((b2, N_CLAUSES), dt)
    cell = lambda dt: shape((K, N_CLAUSES), dt)
    bk = backends.get_backend("pallas")
    _compile_for_chip(
        lambda *a: bk.ta_feedback(*a, interpret=False),
        shape((b2, K), jnp.int8), row(jnp.bool_), row(jnp.bool_),
        row(jnp.bool_), cell(jnp.int32), cell(jnp.int32), cell(jnp.bool_))
    ws = vmem.ta_feedback_working_set(K=K, n_clause=N_CLAUSES, batch2=b2)
    assert ws.total_bytes <= vmem.DEFAULT_VMEM_BUDGET_BYTES, ws


# crossbar_mvm runs the staged stages (single device and the co-resident
# grid) and every per-device stage of the sharded grid.
MVM_STAGES = {
    "clause_stage": (TILE_ROWS, TILE_COLS),       # one clause row-shard
    "class_stage": (CLASS_ROWS, M),               # one class row-shard
    "clause_shard_r2": (784, TILE_COLS),          # R=2 split, per device
    "class_shard_s2": (250, M),                   # S=2 split, per device
}


@pytest.mark.parametrize("stage", list(MVM_STAGES))
def test_crossbar_mvm_compiles_for_v5e(shape, stage):
    rows, cols = MVM_STAGES[stage]
    bk = backends.get_backend("pallas")
    _compile_for_chip(
        lambda d, g: bk.crossbar_mvm(d, g, v_read=1.0, cutoff=0.0,
                                     interpret=False),
        shape((128, rows), jnp.float32), shape((rows, cols), jnp.float32))
    ws = vmem.mvm_working_set(k_rows=rows)
    assert ws.total_bytes <= vmem.DEFAULT_VMEM_BUDGET_BYTES, ws


@pytest.mark.parametrize("metered", [False, True],
                         ids=["sharded", "sharded_metered"])
def test_sharded_grid_compiles_for_v5e_2x2(topo, metered):
    """The README's sharded crossbar grid on all four chips of the
    described host: the R=2 / S=2 split of the paper configuration
    (IMPACTConfig(max_tile_rows=784, max_class_rows=250)) on a
    (data=2, model=2) mesh.  Each device runs ``crossbar_mvm``; the
    digital AND and the class-shard add are all-reduces over "model"."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.sharding.crossbar import fused_impact_shmap

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    rep = NamedSharding(mesh, PartitionSpec())
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                     sharding=rep)
    text = _compile_for_chip(
        lambda *a: fused_impact_shmap(*a, thresh=I_CSA_THRESHOLD, mesh=mesh,
                                      impl="pallas", interpret=False,
                                      meter=metered),
        shape((512, K), jnp.int8), shape((2, 1, 784, TILE_COLS), jnp.float32),
        shape((TILE_COLS,), jnp.bool_), shape((2, 250, M), jnp.float32))
    assert "all-reduce" in text, "no cross-device combine in the executable"
