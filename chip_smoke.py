#!/usr/bin/env python3
"""Chip smoke test: drive the served IMPACT path once, on a TPU, at the
paper's MNIST widths (K=1568 literals, n=500 clauses, m=10 classes).

    python chip_smoke.py                # one chip, phases a-e
    python chip_smoke.py --four-chips   # four chips, the sharded grid only

One chip:
  a. build    train a CoTM on synthetic digits (7 epochs, 6000 rows, the
              schedule of tests/test_system.py) and
              program it onto ideal Y-Flash devices (build_system);
  b. compile  two sessions with interpret=False: the unpacked fused-metered
              serving session (capacity 128) and the 2-bit packed one;
  c. predict  batch 8/128/512 on both sessions: argmax equals a float64
              numpy reference of the fused datapath (kernels/ref.py's
              einsum math); class currents and both per-lane meters agree
              with it within tests/test_fused_impact.py's tolerances; the
              clause bits equal the software CoTM's (core.clause_outputs)
              and hardware accuracy tracks software accuracy (core.predict);
  d. serve    a few hundred literal rows through IMPACTEngine(session).run:
              predictions equal the reference, and per-request read bills
              sum to the batch meter within 1e-9;
  e. train    one OnlineTrainer.update on the unpacked session (the only
              path that runs the ta_feedback kernel): its deltas equal
              kernels.ref.ta_feedback_ref exactly.

Four chips: the same CoTM split into R=2 literal row-shards and S=2 class
row-shards, on a (data=2, model=2) mesh over the four chips, against the
same split system compiled for one chip: argmax equal, currents within
the sharding suites' rtol, operands and outputs spread over all four.

Every phase prints one line naming the device with its compile and wall
seconds.  The last line is ``{"ok": true, "device": {...}}`` only when
every check passed; otherwise the script exits non-zero without it.  It
refuses to run on anything but a TPU: no phase may fall back to the CPU
or to Pallas interpret mode.  Data and weights come from fixed seeds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback
import warnings

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0


@dataclasses.dataclass(frozen=True)
class Dims:
    """The CoTM and traffic sizes a run uses (the paper's MNIST widths)."""
    n_literals: int = 1568
    n_clauses: int = 500
    n_classes: int = 10
    train_rows: int = 6000
    epochs: int = 7
    batches: tuple[int, ...] = (8, 128, 512)
    capacity: int = 128
    serve_rows: int = 384
    update_rows: int = 64
    #: IMPACTConfig tile bounds of the four-chip R=2 / S=2 split.
    split_tile_rows: int = 784
    split_class_rows: int = 250


# Tolerances of tests/test_fused_impact.py (scores; the clause meter sums
# up to R*tr*C*tc f32 terms; the class meter) and of the sharding suites.
RTOL_SCORES = 1e-6
RTOL_CLAUSE_METER = 1e-3
RTOL_CLASS_METER = 1e-5
RTOL_SHARDED_METERS = 1e-5
#: check_perf.py's bound on per-request bills against the batch meter.
BILLING_RTOL = 1e-9
#: tests/test_system.py: hardware accuracy within 3 points of software.
ACC_SLACK = 0.03


class Run:
    """Failure list, compile-time meter and per-phase report lines."""

    def __init__(self, jax):
        self.jax = jax
        self.failures: list[str] = []
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        dev = jax.devices()[0]
        self.device = dict(platform=dev.platform, kind=dev.device_kind,
                           count=len(jax.devices()))

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def check(self, ok, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            print(f"  FAILED: {what}", flush=True)
        return bool(ok)

    def phase(self, name: str, fn, *args):
        """Run one phase; print its device, compile and wall seconds."""
        c0, t0, f0 = self.compile_s, time.perf_counter(), len(self.failures)
        out = fn(*args)
        wall = time.perf_counter() - t0
        d = self.device
        status = "ok" if len(self.failures) == f0 else "FAILED"
        print(f"phase {name}: {status} device={d['platform']}:"
              f"{d['kind']}x{d['count']} compile_s={self.compile_s - c0!r} "
              f"wall_s={wall!r}", flush=True)
        return out


def max_rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(got - want) / scale))


def reference(lits, clause_i, nonempty, class_i, thresh):
    """float64 numpy twin of the fused datapath, written from the einsums
    of ``kernels/ref.py`` (``impact_clause_bits_ref`` +
    ``impact_class_scores_ref``) -> (fired (B, C*tc), class currents
    (B, M), per-lane clause current (B,), per-lane class current (B,))."""
    import numpy as np
    lits = np.asarray(lits, np.float64)
    ci = np.asarray(clause_i, np.float64)
    wi = np.asarray(class_i, np.float64)
    B, K = lits.shape
    R, C, tr, tc = ci.shape
    S, sr, M = wi.shape
    lit = np.ones((B, R * tr))
    lit[:, :K] = lits                    # padding rows float ('Z')
    drive = (1.0 - lit).reshape(B, R, tr)
    i_col = np.stack([drive[:, r] @ ci[r].transpose(1, 0, 2).reshape(
        tr, C * tc) for r in range(R)], axis=1).reshape(B, R, C, tc)
    fired = np.all(i_col < thresh, axis=1).reshape(B, C * tc)
    fired &= np.asarray(nonempty, bool)
    drv = np.zeros((B, S * sr))
    n = min(C * tc, S * sr)
    drv[:, :n] = fired[:, :n]
    i_cls = np.einsum("bsn,snm->bsm", drv.reshape(B, S, sr), wi)
    return fired, i_cls.sum(axis=1), i_col.sum(axis=(1, 2, 3)), \
        i_cls.sum(axis=(1, 2))


# -- phases -------------------------------------------------------------------

def build(run: Run, dims: Dims, impact_cfg=None):
    """(a) Train the CoTM and program it onto ideal devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import CoTMConfig, booleanize, predict, train_epochs
    from repro.data.synthetic import digits
    from repro.impact import IMPACTConfig, build_system

    cfg = CoTMConfig(n_literals=dims.n_literals, n_clauses=dims.n_clauses,
                     n_classes=dims.n_classes, n_states=128, threshold=96,
                     specificity=8.0)
    x_tr, y_tr = digits(dims.train_rows, seed=SEED + 1, jitter=2)
    x_te, y_te = digits(max(dims.batches), seed=SEED + 2, jitter=2)
    lit_tr = booleanize(jnp.asarray(x_tr))
    lit_te = np.asarray(booleanize(jnp.asarray(x_te)), np.int8)
    params = train_epochs(cfg.init(jax.random.key(SEED)), lit_tr,
                          jnp.asarray(y_tr), jax.random.key(SEED + 1), cfg,
                          epochs=dims.epochs, batch_size=32)
    sw_pred = np.asarray(predict(params, jnp.asarray(lit_te), cfg))
    sw_acc = float((sw_pred == y_te).mean())
    # Ideal devices with the paper's two-phase weight tuning: without the
    # fine-tune pass the class cells sit anywhere within +/-20 weight
    # segments of their targets and hardware accuracy no longer tracks
    # software (tests/test_fused_impact.py's golden test uses the same).
    impact_cfg = impact_cfg or IMPACTConfig(variability=False, finetune=True)
    system = build_system(params, cfg, jax.random.key(SEED + 2), impact_cfg)
    print(f"  software accuracy {sw_acc!r} on {len(y_te)} held-out rows; "
          f"clause grid {tuple(system.clause_i.shape)}, class grid "
          f"{tuple(system.class_i.shape)}")
    run.check(sw_acc > 0.2, f"software CoTM accuracy {sw_acc} is not "
              f"above twice chance: training did not run")
    run.check(bool(np.isfinite(np.asarray(system.clause_i)).all()
                   and np.isfinite(np.asarray(system.class_i)).all()),
              "programmed cell currents are not finite")
    return dict(cfg=cfg, params=params, system=system, lit_tr=lit_tr,
                y_tr=np.asarray(y_tr), lit_te=lit_te, y_te=y_te,
                sw_pred=sw_pred, sw_acc=sw_acc)


def compile_sessions(run: Run, dims: Dims, state, interpret: bool):
    """(b) The unpacked serving session and the 2-bit packed session."""
    from repro.impact import RuntimeSpec
    system = state["system"]
    specs = {
        "pallas": RuntimeSpec(backend="pallas", metering="fused",
                              capacity=dims.capacity, interpret=interpret),
        "packed": RuntimeSpec(backend="pallas", packing="2bit",
                              metering="fused", interpret=interpret),
    }
    sessions = {}
    for name, spec in specs.items():
        sess = system.compile(spec)
        for b in dims.batches:
            sess.warm(b, "predict")
            sess.warm(b, "infer_step")
        sessions[name] = sess
        print(f"  {name}: {sess!r}")
    return sessions


def predict_parity(run: Run, dims: Dims, state, sessions):
    """(c) Both sessions against the f64 reference and the software CoTM."""
    import numpy as np
    from repro.core.cotm import clause_outputs, include_mask
    from repro.impact.yflash import I_CSA_THRESHOLD, T_READ, V_READ
    from repro.kernels import packing

    system, cfg = state["system"], state["cfg"]
    lit_te, n = state["lit_te"], cfg.n_clauses
    tr = system.clause_i.shape[2]
    packed = packing.pack_clause_operand(system.clause_i)
    clause_ops = {"pallas": system.clause_i,
                  "packed": packing.dequant_clause(
                      packed.bits, packed.levels, tr)}
    include = include_mask(state["params"].ta_state, cfg.n_states)
    sw_bits = np.asarray(clause_outputs(lit_te, include))
    refs = {}
    for name, sess in sessions.items():
        fired, scores, i_cl, i_cs = reference(
            lit_te, clause_ops[name], system.nonempty, system.class_i,
            I_CSA_THRESHOLD)
        refs[name] = dict(pred=scores.argmax(axis=-1), scores=scores,
                          e_clause=V_READ * i_cl * T_READ,
                          e_class=V_READ * i_cs * T_READ)
        run.check(np.array_equal(fired[:, :n], sw_bits),
                  f"{name}: analog clause bits differ from the software "
                  f"CoTM's in {int((fired[:, :n] != sw_bits).sum())} cells")
        worst = dict(scores=0.0, e_clause=0.0, e_class=0.0)
        for b in dims.batches:
            rows = lit_te[:b]
            res = sess.predict(rows)
            step = sess.infer_step(rows, np.ones((b,), bool))
            pred = np.asarray(res.predictions)
            want = refs[name]
            mism = int((pred != want["pred"][:b]).sum())
            run.check(mism == 0, f"{name} b={b}: {mism} predictions differ "
                      f"from the f64 reference")
            run.check(np.array_equal(np.asarray(step.predictions), pred),
                      f"{name} b={b}: infer_step and predict disagree")
            for key, got, rtol in (
                    ("scores", res.scores, RTOL_SCORES),
                    ("e_clause", step.e_clause_lanes, RTOL_CLAUSE_METER),
                    ("e_class", step.e_class_lanes, RTOL_CLASS_METER)):
                err = max_rel_err(got, want[key][:b])
                worst[key] = max(worst[key], err)
                run.check(err <= rtol, f"{name} b={b}: {key} max rel err "
                          f"{err!r} > {rtol}")
        hw_acc = float((want["pred"] == state["y_te"]).mean())
        agree = float((want["pred"] == state["sw_pred"]).mean())
        print(f"  {name}: max rel err vs f64 reference: scores "
              f"{worst['scores']!r}, clause meter {worst['e_clause']!r}, "
              f"class meter {worst['e_class']!r}; hardware accuracy "
              f"{hw_acc!r} vs software {state['sw_acc']!r}, argmax "
              f"agreement with core.predict {agree!r}")
        run.check(hw_acc >= state["sw_acc"] - ACC_SLACK,
                  f"{name}: hardware accuracy {hw_acc} falls more than "
                  f"{ACC_SLACK} below software {state['sw_acc']}")
    return refs


def serve(run: Run, dims: Dims, state, sessions, refs):
    """(d) Literal rows through the continuous-batching engine."""
    import numpy as np
    from repro.serve import IMPACTEngine

    rows = state["lit_te"][:dims.serve_rows]
    engine = IMPACTEngine(sessions["pallas"])
    preds, stats = engine.run(rows)
    want = refs["pallas"]
    mism = int((np.asarray(preds) != want["pred"][:len(rows)]).sum())
    run.check(mism == 0, f"serve: {mism} of {len(rows)} served predictions "
              f"differ from the reference")
    bills = [r.e_read_j for r in engine.request_records]
    meter = stats["energy"].read_energy_j
    rel = abs(sum(bills) - meter) / meter if meter > 0 else float("inf")
    bill_err = max_rel_err(bills, want["e_clause"][:len(rows)]
                           + want["e_class"][:len(rows)])
    print(f"  served {len(bills)} requests in {stats['batches']} sweeps; "
          f"sum of bills vs batch meter rel err {rel!r}; per-request bill "
          f"vs f64 reference max rel err {bill_err!r}")
    run.check(len(bills) == len(rows), f"serve: {len(bills)} bills for "
              f"{len(rows)} requests")
    run.check(rel <= BILLING_RTOL, f"serve: per-request bills drift {rel!r} "
              f"from the batch meter (> {BILLING_RTOL})")
    run.check(bill_err <= RTOL_CLAUSE_METER, f"serve: per-request bills "
              f"differ from the reference by {bill_err!r}")


def train_online(run: Run, dims: Dims, state, sessions):
    """(e) One in-array update; the ta_feedback kernel against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.train import OnlineTrainer

    sess = sessions["pallas"]
    trainer = OnlineTrainer(sess, state["params"], state["cfg"],
                            key=jax.random.key(SEED + 3), variability=False)
    # Keep the inputs and output of the session's ta_feedback call, so
    # the kernel's deltas can be checked against the oracle.
    calls = []
    kernel_entry = sess.ta_feedback

    def ta_feedback(*args):
        out = kernel_entry(*args)
        calls.append((args, out))
        return out

    sess.ta_feedback = ta_feedback
    try:
        record = trainer.update(state["lit_tr"][:dims.update_rows],
                                state["y_tr"][:dims.update_rows])
    finally:
        del sess.ta_feedback
    run.check(len(calls) == 1, f"train: {len(calls)} ta_feedback calls")
    args, got = calls[0]
    # Integer counts of 0/1 masks: exact at any matmul precision; the
    # highest one keeps the oracle independent of the XLA default.
    with jax.default_matmul_precision("highest"):
        want = ref.ta_feedback_ref(*(jnp.asarray(a) for a in args))
    got, want = np.asarray(got), np.asarray(want)
    diff = int((got != want).sum())
    print(f"  update: {record['n_flips']} TA action flips, "
          f"{record['n_weight_cells']} weight cells re-tuned, write energy "
          f"{record['write_energy_j']!r} J; ta_feedback delta cells "
          f"{got.size}, nonzero {int((got != 0).sum())}, differing from "
          f"the oracle {diff}")
    run.check(diff == 0, f"train: ta_feedback deltas differ from "
              f"ref.ta_feedback_ref in {diff} cells")
    run.check(np.isfinite(record["write_energy_j"])
              and record["write_energy_j"] >= 0.0,
              f"train: write energy {record['write_energy_j']}")


def four_chip_grid(run: Run, dims: Dims, state, interpret: bool):
    """The R=2 / S=2 split grid on a (data=2, model=2) mesh over four
    chips, against the same split system compiled for one chip."""
    import jax
    import numpy as np
    from repro.impact import (IMPACTConfig, RuntimeSpec, Topology,
                              build_system)
    from repro.launch import make_crossbar_mesh

    split = IMPACTConfig(max_tile_rows=dims.split_tile_rows,
                         max_class_rows=dims.split_class_rows,
                         variability=False, finetune=True)
    system = build_system(state["params"], state["cfg"],
                          jax.random.key(SEED + 2), split)
    R, S = system.clause_i.shape[0], system.class_i.shape[0]
    mesh = make_crossbar_mesh(n_model=2)
    spec = dict(backend="pallas", metering="fused", capacity=dims.capacity,
                interpret=interpret)
    one = system.compile(RuntimeSpec(**spec))
    four = system.compile(RuntimeSpec(**spec, topology=Topology(mesh=mesh)))
    devices = set(jax.devices())
    print(f"  split grid R={R} S={S}; mesh {dict(mesh.shape)}; "
          f"plan {four.plan}")
    run.check((R, S) == (2, 2) and four.plan == (True, True),
              f"four chips: expected a fully sharded R=2/S=2 plan, got "
              f"R={R} S={S} plan={four.plan}")
    for b in (dims.capacity, max(dims.batches)):
        rows = state["lit_te"][:b]
        valid = np.ones((b,), bool)
        r1, r4 = one.predict(rows), four.predict(rows)
        s1, s4 = one.infer_step(rows, valid), four.infer_step(rows, valid)
        mism = int((np.asarray(r1.predictions)
                    != np.asarray(r4.predictions)).sum())
        err = max_rel_err(r4.scores, r1.scores)
        err_m = max(max_rel_err(s4.e_clause_lanes, s1.e_clause_lanes),
                    max_rel_err(s4.e_class_lanes, s1.e_class_lanes))
        # Executable operands: literals, clause currents, nonempty mask,
        # class currents.  The two crossbars must be partitioned over the
        # model axis, not replicated.
        in_sh = four._exe("predict", b).input_shardings[0]
        spread = {f"predict operand {i}": len(s.device_set)
                  for i, s in enumerate(in_sh)}
        spread["predict scores"] = len(r4.scores.sharding.device_set)
        spread["infer_step clause meter"] = len(
            s4.e_clause_lanes.sharding.device_set)
        print(f"  b={b}: argmax mismatches {mism}; scores max rel err "
              f"{err!r}; meters max rel err {err_m!r}; devices spanned "
              f"{spread}")
        run.check(mism == 0, f"four chips b={b}: {mism} predictions differ "
                  f"from the one-chip split system")
        run.check(err <= RTOL_SCORES, f"four chips b={b}: scores max rel "
                  f"err {err!r} > {RTOL_SCORES}")
        run.check(err_m <= RTOL_SHARDED_METERS, f"four chips b={b}: "
                  f"meters max rel err {err_m!r} > {RTOL_SHARDED_METERS}")
        narrow = {k: v for k, v in spread.items() if v != len(devices)}
        run.check(not narrow, f"four chips b={b}: not spread over all "
                  f"{len(devices)} devices: {narrow}")
        run.check(not (in_sh[1].is_fully_replicated
                       or in_sh[3].is_fully_replicated),
                  f"four chips b={b}: crossbar operands are replicated, "
                  f"not sharded: {in_sh[1]}, {in_sh[3]}")


# -- entry point ---------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded grid and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from repro.compile_cache import use_compilation_cache
    from repro.impact import SpecDeprecationWarning
    warnings.simplefilter("error", SpecDeprecationWarning)

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); this "
              f"smoke test runs only on the chip", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} TPU devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    cache_dir = use_compilation_cache()
    run = Run(jax)
    dims = Dims()
    print(f"chip_smoke: device {run.device}, jax {jax.__version__}, "
          f"compile cache {cache_dir}", flush=True)
    try:
        state = run.phase("a/build", build, run, dims)
        if args.four_chips:
            run.phase("4chip/sharded-grid", four_chip_grid, run, dims, state,
                      False)
        else:
            sessions = run.phase("b/compile", compile_sessions, run, dims,
                                 state, False)
            refs = run.phase("c/predict", predict_parity, run, dims, state,
                             sessions)
            run.phase("d/serve", serve, run, dims, state, sessions, refs)
            run.phase("e/train", train_online, run, dims, state, sessions)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: a phase raised; see the traceback above",
              file=sys.stderr)
        return 1
    print(f"compile cache: {run.cache_hits} hits, {run.cache_misses} "
          f"misses, {run.compile_s!r} s compiling", flush=True)
    if run.failures:
        print(f"chip_smoke: {len(run.failures)} check(s) failed:",
              file=sys.stderr)
        for f in run.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": run.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
