"""Plain float64 reference of the IMPACT fabric, written from the paper's
semantics and independent of the program under test.

From a planted CoTM (TA states, integer class weights) and the
configuration's device and tile settings it programs ideal Y-Flash cells
(deterministic pulse trains, no variability), converts conductances to
read currents, and runs both crossbars on literal rows:

* clause crossbar: literal 0 drives its row at V_R, literal 1 floats; a
  column fires when its current stays under the CSA threshold in every
  literal row-shard (digital AND), and only if the clause includes at
  least one literal;
* class crossbar: fired clauses drive their rows, and the column currents
  are the class scores (argmax is the prediction);
* read energy: V_R * T_READ * (summed clause column currents + summed
  class column currents) per row.

Unused tile cells are part of the fabric: padded literal rows float,
padded clause columns are programmed to the low state and draw leakage,
padded class rows hold weight 0 and are never driven.

Imports nothing of the program and takes nothing it made.
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np

#: Two classes tie when their float64 scores agree to this share of the
#: larger: ten times the float32 summation error of a class score (about
#: 1e-7 over the fired clauses), so a float32 datapath may pick either,
#: and under the smallest gap between distinct scores seen in the pools
#: (3e-6 over 7 seeds of 8192 rows).  A bfloat16 datapath errs by 1e-3.
TIE_REL = 1e-6


@dataclasses.dataclass(frozen=True)
class Fabric:
    """Programmed read currents in tile layout (float64, amperes)."""
    clause_i: np.ndarray    # (R*tr, C*tc)
    nonempty: np.ndarray    # (C*tc,) bool
    class_i: np.ndarray     # (S*sr, m)
    shards: int             # R, literal row-shards
    thresh: float
    v_read: float
    t_read: float


def _f32(x) -> np.ndarray:
    return np.asarray(x, np.float32)


#: Band edges closer than this many float32 ulps to a conductance are a
#: tie that float rounding decides (see ``program``).
TIE_ULPS = 4


def _exp_candidates(width: float, tau: float, ulps: int) -> list:
    """float32 values within ``ulps`` of exp(-width / tau) as a float32
    controller computes it: the quotient in float32, then an exp that
    need not be correctly rounded."""
    x = np.float32(-width) / np.float32(tau)
    e = np.float32(np.exp(np.float64(x)))
    out = [e]
    lo = hi = e
    for _ in range(ulps):
        lo = np.nextafter(lo, np.float32(0))
        hi = np.nextafter(hi, np.float32(1))
        out += [lo, hi]
    return out


def _pulse_train(g0, lo, hi, *, decay, rate, max_pulses: int, dev: dict,
                 ties_inside: bool, fused: bool = False) -> np.ndarray:
    """Drive ideal cells from ``g0`` into [lo, hi]: program pulses decay
    toward the floor, erase pulses approach the ceiling; a cell inside its
    band takes no pulse.  The controller works in float32, the precision
    the configuration states: floor + (g - floor) * decay and
    g + (ceiling - g) * rate, each rounded once per operation, or, if
    ``fused``, the multiply-add rounded once.  A conductance within
    ``TIE_ULPS`` of a band edge counts as inside the band if
    ``ties_inside``, else outside."""
    decay = np.clip(_f32(decay), 0, 1)
    rate = np.clip(_f32(rate), 0, 1)
    floor, ceil = _f32(dev["g_min_s"]), _f32(dev["g_max_s"])

    def madd(a, b, c):           # a * b + c in float32
        if fused:                # exact product in float64, one rounding
            return (a.astype(np.float64) * np.float64(b)
                    + c.astype(np.float64)).astype(np.float32)
        return (a * b + c).astype(np.float32)

    lo, hi = _f32(lo), _f32(hi)
    sign = 1 if ties_inside else -1
    eps = np.finfo(np.float32).eps * TIE_ULPS
    finite = lambda x: np.where(np.isinf(x), 0, np.abs(x))
    hi_t = hi + sign * eps * finite(hi)
    lo_t = lo - sign * eps * finite(lo)
    g = np.broadcast_to(_f32(g0), lo.shape).copy()
    for _ in range(max_pulses):
        high, low = g > hi_t, g < lo_t
        if not (high.any() or low.any()):
            break
        g = np.where(high, madd(g - floor, decay, np.full_like(g, floor)),
                     np.where(low, madd(ceil - g, rate, g), g))
    return g


def _read_current(g: np.ndarray, dev: dict) -> np.ndarray:
    """Read current (float64) of float32 conductances: I = G * V_R, with
    the low-conductance nonlinearity below the cutoff."""
    nl = np.where(g < _f32(dev["g_nonlin_cutoff_s"]),
                  _f32(dev["lcs_nonlinearity"]), _f32(1.0))
    return ((g * _f32(dev["v_read_v"])) * nl).astype(np.float64)


#: How many float32 ulps a controller's exp may miss the correctly
#: rounded value by (see ``program``).
EXP_ULPS = 1


def program(ta_state: np.ndarray, weights: np.ndarray, cfg: dict
            ) -> list[Fabric]:
    """Program the fabric for TA states (K, n) and signed weights (m, n).

    The configuration leaves two things to float32 rounding, and the
    reference returns one fabric for each valid outcome (equal ones once):

    * a tie: the erased conductance equals the top of the analog range,
      so the weight level ``w_max - pretune_tol`` has its band's upper
      edge exactly on the cell's start value, and a float32 controller
      may or may not pulse it (ties read as inside, and as outside);
    * the pulse factors exp(-width / tau): a float32 exp need not be
      correctly rounded, and a class level compounds its factor over
      several pulses (each factor within ``EXP_ULPS``);
    * whether each pulse's multiply-add is fused (rounded once).

    The clause tile takes the correctly rounded factor: its currents
    enter the CSA decisions, whose margins are wide, and the clause
    bills, which no limit holds.  All fabrics share its arrays."""
    dev, tile, prog = cfg["device"], cfg["tile"], cfg["programming"]
    ta_state = np.asarray(ta_state, np.int64)
    weights = np.asarray(weights, np.int64)
    K, n = ta_state.shape
    m = weights.shape[0]
    tr, tc, sr = (tile["max_tile_rows"], tile["max_tile_cols"],
                  tile["max_class_rows"])
    R, C, S = -(-K // tr), -(-n // tc), -(-n // sr)

    include = np.zeros((R * tr, C * tc), bool)
    include[:K, :n] = ta_state > cfg["n_states"]
    nonempty = include.any(axis=0)
    # Boolean mode: included cells stay erased (>= the HCS threshold),
    # excluded and unused cells are programmed below the LCS threshold.
    # Every cell of a kind takes the same pulse train, so program one.
    cl = prog["clause"]
    w = cl["pulse_width_s"]
    kinds = _pulse_train(
        dev["g_erased_s"], [dev["g_hcs_bool_s"], 0.0],
        [np.inf, dev["g_lcs_s"]],
        decay=_exp_candidates(w, dev["tau_prog_s"], 0)[0],
        rate=_f32(1) - _exp_candidates(w, dev["tau_erase_s"], 0)[0],
        max_pulses=cl["max_pulses"], dev=dev, ties_inside=True)
    clause_i = np.where(include, *_read_current(kinds, dev))

    # Analog mode: shift signed weights to unipolar, map each integer
    # level onto [G_lo, G_hi] in w_max segments, tune to a band around it.
    w_uni = weights + max(-weights.min(), 0)
    w_max = max(int(w_uni.max()), 1)
    w_pad = np.zeros((S * sr, m), np.int64)     # unused rows: level 0
    w_pad[:n] = w_uni.T
    levels = [_class_levels(w_max, cfg, ties_inside=t, choice=c)
              for t in (True, False) for c in _factor_choices(cfg)]
    fabs, seen = [], []
    for level_i in levels:
        if any(np.array_equal(level_i, x) for x in seen):
            continue
        seen.append(level_i)
        fabs.append(Fabric(clause_i=clause_i, nonempty=nonempty,
                           class_i=level_i[w_pad], shards=R,
                           thresh=dev["i_csa_threshold_a"],
                           v_read=dev["v_read_v"], t_read=dev["t_read_s"]))
    return fabs


def _factor_choices(cfg: dict) -> list[dict]:
    """Every choice of the class tile's float32 pulse factors."""
    dev, cp = cfg["device"], cfg["programming"]["class"]
    phases = [("pretune", cp["pretune_width_s"])]
    if cp["finetune"]:
        phases.append(("finetune", cp["finetune_width_s"]))
    axes = []
    for name, width in phases:
        axes.append([(name + "_decay", d) for d in
                     _exp_candidates(width, dev["tau_prog_s"], EXP_ULPS)])
        axes.append([(name + "_rate", _f32(1) - e) for e in
                     _exp_candidates(width, dev["tau_erase_s"], EXP_ULPS)])
    axes.append([("fused", False), ("fused", True)])
    return [dict(combo) for combo in itertools.product(*axes)]


def _class_levels(w_max: int, cfg: dict, *, ties_inside: bool,
                  choice: dict) -> np.ndarray:
    """Read current (float64) of each weight level 0..w_max after the
    two-phase tuning, for one choice of pulse factors."""
    dev, cp = cfg["device"], cfg["programming"]["class"]
    g_lo, g_hi = dev["g_range_lo_s"], dev["g_range_hi_s"]
    seg = (g_hi - g_lo) / w_max
    frac = _f32(np.arange(w_max + 1)) / _f32(w_max)
    target = _f32(g_lo) + frac * _f32(g_hi - g_lo)
    tol = _f32(cp["pretune_tol_segments"] * seg)
    g = _pulse_train(dev["g_erased_s"], target - tol, target + tol,
                     decay=choice["pretune_decay"],
                     rate=choice["pretune_rate"],
                     max_pulses=cp["max_pulses"], dev=dev,
                     ties_inside=ties_inside, fused=choice["fused"])
    if cp["finetune"]:
        tol = _f32(cp["finetune_tol_segments"] * seg)
        g = np.concatenate([
            _pulse_train(gi, [t - tol], [t + tol],
                         decay=choice["finetune_decay"],
                         rate=choice["finetune_rate"],
                         max_pulses=cp["max_pulses"], dev=dev,
                         ties_inside=ties_inside, fused=choice["fused"])
            for gi, t in zip(g, target)])
    return _read_current(g, dev)


def infer(fabs: list[Fabric], literals: np.ndarray,
          block: int = 1024) -> list[dict]:
    """Rows (B, K) of 0/1 literals -> for each fabric, float64 ``scores``
    (B, m), ``e_clause`` / ``e_class`` read energies (B,) in joules, and
    the ``fired`` share of clause columns.  Runs in blocks of rows; the
    clause crossbar, which the fabrics share, is run once."""
    fab0 = fabs[0]
    rows_pad, cols = fab0.clause_i.shape
    B, K = literals.shape
    outs = [dict(scores=[], e_clause=[], e_class=[], fired=[]) for _ in fabs]
    for b0 in range(0, B, block):
        lit = np.asarray(literals[b0:b0 + block], np.float64)
        drive = np.zeros((lit.shape[0], rows_pad))
        drive[:, :K] = 1.0 - lit
        fired = np.broadcast_to(fab0.nonempty, (lit.shape[0], cols)).copy()
        i_clause = np.zeros(lit.shape[0])
        for idx in np.split(np.arange(rows_pad), fab0.shards):
            i_col = drive[:, idx] @ fab0.clause_i[idx]
            fired &= i_col < fab0.thresh
            i_clause += i_col.sum(axis=1)
        vt = fab0.v_read * fab0.t_read
        for fab, out in zip(fabs, outs):
            drv = np.zeros((lit.shape[0], fab.class_i.shape[0]))
            k = min(cols, drv.shape[1])
            drv[:, :k] = fired[:, :k]
            scores = drv @ fab.class_i
            out["scores"].append(scores)
            out["e_clause"].append(vt * i_clause)
            out["e_class"].append(vt * scores.sum(axis=1))
            out["fired"].append(fired.mean(axis=1))
    return [{key: np.concatenate(v) for key, v in out.items()}
            for out in outs]


def wrong_predictions(pred: np.ndarray, scores: np.ndarray) -> np.ndarray:
    """Mask of predictions that are not a best class of the reference
    (any of the tied best classes is right)."""
    pred = np.asarray(pred, np.int64)
    best = scores.max(axis=1)
    ok = (pred >= 0) & (pred < scores.shape[1])
    got = scores[np.arange(len(pred)), np.clip(pred, 0, scores.shape[1] - 1)]
    tol = TIE_REL * np.maximum(np.abs(best), np.abs(got))
    return ~ok | (got < best - tol)


def rel_err(got, want) -> np.ndarray:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(np.abs(want), np.finfo(float).tiny)


def energy_numbers(got, want) -> dict:
    """``class_energy_err``: the median relative gap of batch class-crossbar
    energies from the reference's.  The class crossbar sums few currents
    (the fired clauses' weights), so float32 summation adds little, and a
    contraction below float32 shows in every batch."""
    err = rel_err(got, want)
    return dict(class_energy_err=float(np.median(err)) if err.size else 0.0)


def bill_numbers(got, want) -> dict:
    """How bills read against the reference's: ``bill_bias``, the size of
    the mean signed relative gap (a precision below the configuration's
    shifts every bill the same way), and the median and widest absolute
    relative gaps (float32 summation in a sound meter scatters each bill
    by a few 1e-7 either way)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return dict(bill_bias=0.0, bill_err_median=0.0, bill_err_max=0.0)
    signed = (got - want) / np.maximum(np.abs(want), np.finfo(float).tiny)
    err = np.abs(signed)
    return dict(bill_bias=float(abs(signed.mean())),
                bill_err_median=float(np.median(err)),
                bill_err_max=float(err.max()))


def margin(scores: np.ndarray) -> float:
    """Smallest relative gap between a row's best score and its best
    score that does not tie with it: how close the traffic comes to a
    decision that rounding could flip."""
    s = np.sort(scores, axis=1)
    best = s[:, -1:]
    tol = TIE_REL * np.abs(best)
    gaps = np.where(s < best - tol, best - s, np.inf).min(axis=1)
    scale = np.maximum(np.abs(best[:, 0]), np.finfo(float).tiny)
    return float(np.min(gaps / scale))
